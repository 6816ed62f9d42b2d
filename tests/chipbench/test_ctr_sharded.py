"""The feature-sharded FTRL cell (``ctr_sharded.train``) at a size a CPU
holds: four host devices stand in for the four chips, in a fresh
interpreter each (the device count is fixed when JAX starts, and this
process keeps its one device).

* the harness finds the cell's configuration, traffic, training module,
  limits and readers by name, from the files the cell added;
* the sharded round program, run by the cell's own ``train_mesh`` over
  three rounds of ``ctr_hashed`` data, agrees with the plain dense reference;
  the bfloat16 reference and the planted faults do not;
* the touched-ids reference is the dense one;
* the mesh state is built slab by slab: no compiled program holds the
  whole padded ``[d_pad, cols]`` state.
"""

import copy
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from chipbench import corpus, harness, spec
from chipbench.drivers import train
from chipbench.references import ftrl_proximal, ftrl_proximal_touched

ROOT = spec.ROOT
WORKLOAD, CONFIG = "ctr_sharded.train", "ctr_criteo_tb_sharded"
DIM = 2**12
# float32 throughout on both sides, the same updates in the same order; only
# the norms' reduction trees differ (a slab at a time against the whole
# vector), a few ulps
SOUND = 1e-5
MESH_READERS = {
    "margin_us.train", "shard_step_roofline.train", "shard_flush_roofline.train",
    "shard_idle_share.train",
}


def small(backend="reference", round_len=32, n_rounds=5):
    c = copy.deepcopy(spec.config(CONFIG))
    c["data"]["dim"] = DIM
    c["data"]["cardinalities"] = [min(x, 2000) for x in c["data"]["cardinalities"]]
    c["train"]["round_len"] = round_len
    c["backend"] = backend
    c["n_examples"] = n_rounds * round_len * c["train"]["batch"]
    return c


def on_four_devices(script: str, *args, timeout: int = 600, xla_flags: str = "") -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count=4 {xla_flags}".strip()
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", script, *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT,
    )
    assert proc.returncode == 0, f"rc={proc.returncode}\n{proc.stdout}\n{proc.stderr[-4000:]}"
    return proc.stdout


def test_the_harness_finds_the_cell_from_its_files():
    run = harness.make_run(WORKLOAD, 2**40 + 3, 1.0, True)
    assert run.cell["chips"] == 4 and run.config["name"] == CONFIG
    assert run.traffic == {**spec.traffic("train_stream"), "driver": "train_mesh"}
    assert set(run.limits) == {"loss_gap", "state1_gap", "change3_gap"}
    assert (ROOT / "chipbench" / "drivers" / "train_mesh.py").exists()
    assert {m["name"] for m in spec.per_layer(run.bench, WORKLOAD)} == MESH_READERS
    assert all(callable(spec.reader(m)) for m in MESH_READERS)
    assert [m["name"] for m in spec.end_to_end(run.bench, WORKLOAD)] == [
        "train_ex_per_s", "setup_s",
    ]
    # the Criteo configuration's keys, at d = 2^30, over four chips
    base = spec.config("ctr_criteo_hashed")
    assert set(run.config) == set(base) | {"mesh", "shard_margin"}
    assert run.config["mesh"] == 4 and run.config["shard_margin"] == "exact"
    assert run.config["data"]["dim"] == 2**30 and run.config["p_max"] == base["p_max"]
    assert sum(run.config["data"]["cardinalities"]) == 187_767_399
    assert run.config["train"] == base["train"] and run.config["reduced"] == ["n_examples"]


ROUNDS = r"""
import json, sys
import jax.numpy as jnp
from chipbench.drivers import train, train_mesh
from chipbench.references import ftrl_proximal

c = json.loads(sys.argv[1])
data = train_mesh.blocks(c, 11, 3, (c["train"]["round_len"], c["train"]["batch"]))
assert len(data["idx"].sharding.device_set) == 4
prog = train_mesh.Program(c, data)
slabs = [s.data.shape for s in prog.state.wpsi.addressable_shards]
assert slabs == [(c["data"]["dim"] // 4, 3)] * 4, slabs
readings, kept = prog.first_rounds(3)
hp = train.reference_hypers(c)
dense = ftrl_proximal.train(hp, c["data"]["dim"], kept)
low = ftrl_proximal.train(hp, c["data"]["dim"], kept, jnp.bfloat16)
faults = {}
for f in ("half_batch", "unchanged"):
    bad, _ = train_mesh.Program(c, data, f).first_rounds(3)
    faults[f] = train.compare(bad, dense, c["params"])
print(json.dumps({
    "sound": train.compare(readings, dense, c["params"]),
    "touched": train.compare(readings, train.reference_rounds(c, kept), c["params"]),
    "control": train.compare(low, dense, c["params"]),
    "faults": faults,
}))
"""


@pytest.fixture(scope="module", params=["reference", "pallas"])
def rounds(request):
    """The sharded program's first three rounds against the references, on
    the reference backend and on the Pallas kernels (interpreted)."""
    out = on_four_devices(ROUNDS, json.dumps(small(request.param)))
    return json.loads(out.strip().splitlines()[-1])


def test_sharded_rounds_match_the_dense_reference(rounds):
    assert all(v <= SOUND for v in rounds["sound"].values()), rounds["sound"]
    # the check the cell makes, with its own reference
    assert all(v <= SOUND for v in rounds["touched"].values()), rounds["touched"]


def test_the_bf16_reference_fails_a_number(rounds):
    assert max(rounds["control"].values()) > SOUND, rounds["control"]
    correct, checks = harness.verdict(rounds["control"], spec.limits(WORKLOAD))
    assert not correct, checks


@pytest.mark.parametrize("fault", ["half_batch", "unchanged"])
def test_a_planted_fault_fails_the_cell(rounds, fault):
    correct, checks = harness.verdict(rounds["faults"][fault], spec.limits(WORKLOAD))
    assert not correct, checks


def test_the_touched_reference_is_the_dense_one():
    c = small(round_len=64)
    data = corpus.blocks(c, 5, 3, (64, 8))
    kept = [{k: v[r] for k, v in data.items()} for r in range(3)]
    hp = train.reference_hypers(c)
    dense = ftrl_proximal.train(hp, DIM, kept)
    touched = ftrl_proximal_touched.train(hp, DIM, kept)
    assert [r["loss"] for r in touched] == [r["loss"] for r in dense]
    for t, d in zip(touched, dense):
        assert t["leaves"].keys() == d["leaves"].keys()
        for k, v in d["leaves"].items():
            assert t["leaves"][k] == pytest.approx(v, rel=1e-6), k
    with pytest.raises(ValueError):
        ftrl_proximal_touched.train(hp, 100, kept)
    low = ftrl_proximal_touched.train(hp, DIM, kept, jnp.bfloat16)
    assert max(train.compare(low, dense, c["params"]).values()) > SOUND


CELL = r"""
import json, sys
from chipbench import harness

c = json.loads(sys.argv[1])
out = {}
for fault, trace in ((None, False), (None, True), ("half_batch", False)):
    r = harness.run_cell(
        "ctr_sharded.train", 2**35 + 1, 0.2, trace, config=c, require_chip=False,
        fault=fault, out_dir=sys.argv[2], emit=lambda line: None,
    )
    out[f"{fault}-{trace}"] = r
print(json.dumps(out))
"""


def test_the_cell_runs_through_the_harness(tmp_path):
    """A sound run is correct, a planted fault is not, and a traced run on
    the CPU (whose trace names no chip's ops) leaves the readers' metrics
    out rather than fail."""
    out = on_four_devices(CELL, json.dumps(small(n_rounds=6)), str(tmp_path))
    res = json.loads(out.strip().splitlines()[-1])
    sound = res["None-False"]
    assert sound["correct"] is True, sound["checks"]
    assert set(sound["metrics"]) == {"train_ex_per_s", "setup_s"}
    assert sound["device"]["count"] == 4 and sound["attempted"] > 0
    assert res["None-True"]["correct"] is True and res["None-True"]["metrics"] == {}
    assert res["half_batch-False"]["correct"] is False


INIT = r"""
import sys
import numpy as np
from repro.core import LinearConfig, init_state

cfg = LinearConfig(dim=int(sys.argv[1]), solver=sys.argv[2], mesh=4, round_len=16)
w0 = None if sys.argv[3] == "none" else np.linspace(-1, 1, cfg.dim).astype(np.float32)
state = init_state(cfg, w0)
print([s.data.shape for s in state.wpsi.addressable_shards])
"""


@pytest.mark.parametrize("solver, cols", [("ftrl", 3), ("fobos", 2)])
@pytest.mark.parametrize("w0", ["none", "seeded"])
def test_the_mesh_state_is_built_slab_by_slab(tmp_path, solver, cols, w0):
    """Every program compiled while the state is made and placed (XLA's
    dump of each, after optimization: per-device shapes) holds the
    ``[ds, cols]`` slab and never the whole padded ``[d_pad, cols]``."""
    dim, ds = 4094, 1024
    out = on_four_devices(
        INIT, str(dim), solver, w0,
        xla_flags=f"--xla_dump_to={tmp_path} --xla_dump_hlo_as_text",
    )
    assert out.strip() == str([(ds, cols)] * 4)
    texts = [p.read_text() for p in sorted(tmp_path.glob("*after_optimizations.txt"))]
    assert texts
    whole = [f"f32[{n},{cols}]" for n in (dim, 4 * ds)]
    assert not [w for t in texts for w in whole if w in t]
    assert any(f"f32[{ds},{cols}]" in t for t in texts)
