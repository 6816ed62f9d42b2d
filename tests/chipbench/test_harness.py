"""The harness finds a cell's files by name, fails without a TPU, and sees
``correct`` come out false when the timed path is broken underneath."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness, spec

ROOT = spec.ROOT


def _small_train(small_config, name):
    c = small_config(name)
    c["n_examples"] = 5 * 32 * 8
    return c


def _small_serve(small_config):
    c = _small_train(small_config, "medline_bow")
    c["serve"]["round_len"] = 64
    return c


SERVE_TRAFFIC = {"driver": "serve", "arrivals": "poisson", "rate_per_s": 100.0, "trace_seconds": 0.5}
SERVE_LIMITS = {"pred_gap": {"limit": 1e-4}, "w_gap": {"limit": 1e-4}}


def _quiet(_line):
    pass


def _hashes(root: Path) -> dict:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }


def _serve_cell(root: Path, small_config) -> str:
    """A serving cell added to a copy of the benchmark under ``root`` from
    new files alone: its configuration, traffic mix and limits."""
    shutil.copytree(ROOT / "chipbench", root / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    base = root / "chipbench"
    config = _small_serve(small_config)
    config["name"] = "tiny_bow"
    (base / "configs" / "tiny_bow.json").write_text(json.dumps(config))
    (base / "traffic" / "tiny_poisson.json").write_text(json.dumps(SERVE_TRAFFIC))
    (base / "limits" / "tiny.serve.json").write_text(json.dumps(SERVE_LIMITS))
    bench = spec.benchmark()
    bench["configs"].append({"name": "tiny_bow", "source": "test", "file": "chipbench/configs/tiny_bow.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.serve", "config": "tiny_bow", "traffic": "tiny_poisson", "chips": 1, "why": "test"})
    for name in ("predict_p50_ms", "predict_p99_ms", "learn_lag_p99_ms"):
        bench["end_to_end"].append({"name": name, "unit": "ms", "better": "lower", "bound": 0.25, "source": "host_clock", "workloads": ["tiny.serve"]})
    for name in ("device_idle_share.serve", "dispatches_per_req.serve"):
        bench["per_layer"].append({"name": name, "unit": "%", "better": "lower", "source": "device_trace", "layer": "Device", "moves": "predict_p99_ms", "workloads": ["tiny.serve"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny.serve"


def test_a_cell_is_found_by_name_from_new_files(tmp_path, small_config):
    """A new configuration, traffic mix, limits and per-layer metric, added
    as files beside the others, run through the harness with no file of the
    harness edited: only BENCHMARK.json, the index, gains entries."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(tmp_path / "chipbench")
    base = tmp_path / "chipbench"
    config = _small_train(small_config, "medline_bow")
    config["name"] = "tiny_bow"
    (base / "configs" / "tiny_bow.json").write_text(json.dumps(config))
    (base / "traffic" / "short_stream.json").write_text(
        json.dumps({"driver": "train", "check_rounds": 3, "ahead_seconds": 0.5, "ahead_rounds_max": 2,
                    "trace_rounds": 2})
    )
    (base / "limits" / "tiny.train.json").write_text(
        json.dumps({k: {"limit": 1e-4} for k in ("loss_gap", "state1_gap", "change3_gap")})
    )
    (base / "metrics" / "rounds_traced.train.py").write_text(
        "def read(r):\n    return float(r.rounds) if r.rounds else None\n"
    )
    bench = spec.benchmark()
    bench["configs"].append({"name": "tiny_bow", "source": "test", "file": "chipbench/configs/tiny_bow.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.train", "config": "tiny_bow", "traffic": "short_stream", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "rounds_traced.train", "unit": "rounds", "better": "higher", "source": "program_counter", "layer": "Trainer", "moves": "train_ex_per_s", "workloads": ["tiny.train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    run = harness.make_run("tiny.train", 3, 0.2, True, root=tmp_path, out_dir=tmp_path / "out")
    assert run.config["name"] == "tiny_bow" and run.traffic["ahead_rounds_max"] == 2
    assert run.limits["loss_gap"]["limit"] == 1e-4
    assert [m["name"] for m in spec.per_layer(bench, "tiny.train")][-1] == "rounds_traced.train"
    result = harness.run_cell(
        "tiny.train", 3, 0.2, True, root=tmp_path, out_dir=tmp_path / "out",
        require_chip=False, emit=_quiet,
    )
    assert result["correct"] is True
    assert result["metrics"]["rounds_traced.train"] == {"value": 2.0, "unit": "rounds"}
    assert list(result)[-1] == "checks"
    after = _hashes(tmp_path / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "medline.train", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths."""
    bench = spec.benchmark()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr


@pytest.mark.parametrize(
    "workload, config, fault",
    [
        ("medline.train", "medline_bow", None),
        ("medline.train", "medline_bow", "unchanged"),
        ("medline.train", "medline_bow", "half_batch"),
        ("ctr.train", "ctr_criteo_hashed", None),
        ("ctr.train", "ctr_criteo_hashed", "unchanged"),
        ("ctr.train", "ctr_criteo_hashed", "half_batch"),
    ],
)
def test_train_cells_see_a_broken_step(workload, config, fault, tmp_path, small_config):
    """The cell's own limits pass a sound small run and fail each fault."""
    result = harness.run_cell(
        workload, 7, 0.2, False, config=_small_train(small_config, config), require_chip=False,
        fault=fault, out_dir=tmp_path, emit=_quiet,
    )
    assert result["correct"] is (fault is None), result["checks"]


@pytest.mark.parametrize("fault", [None, "unchanged", "altered_answer"])
def test_serve_cell_sees_a_broken_service(fault, tmp_path, small_config):
    cell = _serve_cell(tmp_path / "bench", small_config)
    result = harness.run_cell(
        cell, 7, 0.5, False, root=tmp_path / "bench", require_chip=False, fault=fault,
        out_dir=tmp_path / "out", emit=_quiet,
    )
    assert result["correct"] is (fault is None), result["checks"]
    assert result["attempted"] > 10
    assert set(result["metrics"]) == {"predict_p50_ms", "predict_p99_ms", "learn_lag_p99_ms", "setup_s"}
