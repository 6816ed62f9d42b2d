"""JSONL run logs: RunLogger -> file -> schema.load round-trips clean, the
active-logger stack installs/uninstalls correctly, malformed files are
flagged line-by-line, and ``python -m repro.obs.report`` reproduces the
lazy-work table (work ratio, effective speedup, nnz trajectory) from the
events alone."""
import json
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import report, schema


def _write_lines(path, lines):
    path.write_text("\n".join(lines) + "\n")
    return str(path)


class TestRoundTrip:
    def test_run_logger_round_trip(self, tmp_path):
        path = tmp_path / "run.jsonl"
        with obs.run_logger(str(path), "train", d=512, arch="tiny") as logger:
            assert obs.active_logger() is logger
            logger.metrics({"steps": 10, "loss_ema": 0.5}, step=10)
            logger.span("train.round", 0.25, round=1)
            # numpy payloads must coerce, not crash json
            logger.event("flush", step=np.int64(8), nnz=np.int32(17))
        assert obs.active_logger() is None

        events, errors = schema.load(str(path))
        assert errors == []
        kinds = [e["kind"] for e in events]
        assert kinds == ["run_meta", "metrics", "span", "event"]
        assert events[0]["program"] == "train"
        assert events[0]["d"] == 512
        assert events[0]["meta"] == {"arch": "tiny"}
        assert events[1]["data"]["loss_ema"] == 0.5
        assert events[1]["step"] == 10
        assert events[2]["name"] == "train.round"
        assert events[2]["attrs"] == {"round": 1}
        assert events[3]["data"] == {"step": 8, "nnz": 17}
        for e in events:  # every event carries both stamps
            assert isinstance(e["ts"], float) and isinstance(e["t"], float)

    def test_span_event_carries_its_start_on_the_profiler_clock(self, tmp_path):
        path = tmp_path / "run.jsonl"
        with obs.run_logger(str(path), "train") as logger:
            before = time.time_ns()
            with obs.span("train.run", steps=3):
                pass
            after = time.time_ns()
            logger.span("train.round", 0.25)  # a caller-timed span has no start
        events, errors = schema.load(str(path))
        assert errors == []
        assert before <= events[1]["start_ns"] <= after
        assert events[1]["attrs"] == {"steps": 3}
        assert "start_ns" not in events[2]
        errs = schema.validate_event(
            {"kind": "span", "ts": 1.0, "t": 0.0, "name": "x", "dur_s": 0.1, "attrs": {},
             "start_ns": 1.5}
        )
        assert any("span.start_ns must be an int" in e for e in errs)

    def test_span_start_lines_up_with_its_profile_annotation(self, tmp_path):
        """The logged start and the profiler's record of the annotation agree:
        a profile's events sit at its ``profile_start_time`` plus their own
        start.  The bound is loose for a loaded test machine; another clock
        (the monotonic one) would miss by years."""
        from jax.profiler import ProfileData

        log = tmp_path / "run.jsonl"
        with obs.run_logger(str(log), "train"):
            with obs.profile_to(str(tmp_path / "prof")):
                with obs.span("train.aligned"):
                    time.sleep(0.001)
        start_ns = schema.load(str(log))[0][1]["start_ns"]
        pb = sorted((tmp_path / "prof").rglob("*.xplane.pb"))[-1]
        pd = ProfileData.from_file(str(pb))
        t0 = [v for p in pd.planes for k, v in p.stats if k == "profile_start_time"]
        starts = [
            int(ev.start_ns)
            for p in pd.planes
            for line in p.lines
            for ev in line.events
            if ev.name == "train.aligned"
        ]
        assert len(t0) == 1 and len(starts) == 1, (t0, starts)
        assert abs(t0[0] + starts[0] - start_ns) < 50_000_000

    def test_none_path_is_noop(self):
        with obs.run_logger(None, "train") as logger:
            assert logger is None
            assert obs.active_logger() is None

    def test_nested_loggers_innermost_wins(self, tmp_path):
        with obs.run_logger(str(tmp_path / "a.jsonl"), "a") as outer:
            with obs.run_logger(str(tmp_path / "b.jsonl"), "b") as inner:
                assert obs.active_logger() is inner
            assert obs.active_logger() is outer


class TestSchemaValidation:
    def test_unknown_kind(self):
        errs = schema.validate_event({"kind": "bogus", "ts": 1.0, "t": 0.0}, 3)
        assert errs and "line 3" in errs[0] and "bogus" in errs[0]

    def test_missing_field_and_bad_type(self):
        errs = schema.validate_event({"kind": "span", "ts": 1.0, "t": 0.0, "dur_s": "x"})
        assert any("missing required field 'name'" in e for e in errs)
        assert any("span.dur_s has type str" in e for e in errs)
        # bools never satisfy a numeric stamp
        errs = schema.validate_event({"kind": "metrics", "ts": True, "t": 0.0, "data": {}})
        assert any("metrics.ts" in e for e in errs)

    def test_load_flags_bad_lines(self, tmp_path):
        good = json.dumps({"kind": "run_meta", "ts": 1.0, "t": 0.0, "program": "x", "meta": {}})
        path = _write_lines(tmp_path / "bad.jsonl", [good, "{not json", '{"kind": "nope"}'])
        events, errors = schema.load(path)
        assert len(events) == 2  # the parseable ones, valid or not
        assert any("line 2: not valid JSON" in e for e in errors)
        assert any("line 3" in e and "nope" in e for e in errors)

    def test_load_empty_and_meta_first(self, tmp_path):
        _, errors = schema.load(_write_lines(tmp_path / "empty.jsonl", [""]))
        assert any("empty run log" in e for e in errors)
        metrics_only = json.dumps({"kind": "metrics", "ts": 1.0, "t": 0.0, "data": {}})
        _, errors = schema.load(_write_lines(tmp_path / "nometa.jsonl", [metrics_only]))
        assert any("first event must be run_meta" in e for e in errors)


def _synthetic_run(tmp_path):
    """A hand-written run log with known lazy-work numbers: d=512, 24 steps,
    touched 6 coords/step -> work ratio 144/(512*24), speedup 512/6."""
    d, steps, per_step = 512, 24, 6
    touched = steps * per_step
    mid = {
        "steps": 16,
        "touched_coords": 16 * per_step,
        "nnz": 25,
        "flushes": 2,
        "examples": 32,
        "d": d,
        "span_hist": [16, 80, 0],
    }
    final = {
        "steps": steps,
        "touched_coords": touched,
        "nnz": 20,
        "flushes": 3,
        "examples": 48,
        "d": d,
        "solver": "fobos",
        "loss_mean": 0.6,
        "loss_ema": 0.55,
        "span_hist": [24, 120, 0],
    }
    lines = [
        {"kind": "run_meta", "ts": 1.0, "t": 0.0, "program": "train", "d": d, "meta": {}},
        {"kind": "event", "ts": 1.1, "t": 0.1, "name": "flush", "data": {"step": 8, "nnz": 30}},
        {"kind": "metrics", "ts": 1.2, "t": 0.2, "step": 16, "data": mid},
        {"kind": "metrics", "ts": 1.3, "t": 0.3, "step": steps, "data": final},
        {"kind": "span", "ts": 1.4, "t": 0.4, "name": "train.run", "dur_s": 0.4, "attrs": {}},
    ]
    path = _write_lines(tmp_path / "run.jsonl", [json.dumps(e) for e in lines])
    return path, d, steps, touched


class TestReport:
    def test_summarize_lazy_work(self, tmp_path):
        path, d, steps, touched = _synthetic_run(tmp_path)
        events, errors = schema.load(path)
        assert errors == []
        summary = report.summarize_run(events)
        lw = summary["lazy_work"]
        assert lw["d"] == d
        assert lw["steps"] == steps
        assert lw["touched_coords"] == touched
        assert lw["dense_coords"] == d * steps
        assert lw["work_ratio"] == pytest.approx(touched / (d * steps))
        assert lw["effective_speedup"] == pytest.approx(d * steps / touched)
        assert lw["solver"] == "fobos"
        # trajectory merges flush events and periodic metrics pulls in order
        traj = summary["nnz_trajectory"]
        assert [(p["step"], p["nnz"]) for p in traj] == [(8, 30), (16, 25), (24, 20)]
        assert summary["spans"]["train.run"] == {"count": 1, "total_s": 0.4}

    def test_render_table(self, tmp_path):
        path, d, steps, touched = _synthetic_run(tmp_path)
        events, _ = schema.load(path)
        text = report.render(report.summarize_run(events))
        assert "lazy-work accounting (fobos)" in text
        assert f"{touched / (d * steps):.6f}" in text
        assert f"{d * steps / touched:.1f}x" in text
        assert "[1,2)" in text  # span bucket 1 label
        assert "nnz trajectory" in text

    def test_serve_only_log_degrades(self, tmp_path):
        """A log with no lazy counters still summarizes (spans only)."""
        meta = {"kind": "run_meta", "ts": 1.0, "t": 0.0, "program": "serve", "meta": {}}
        span = {
            "kind": "span",
            "ts": 1.1,
            "t": 0.1,
            "name": "serve.traffic",
            "dur_s": 0.1,
            "attrs": {},
        }
        lines = [json.dumps(meta), json.dumps(span)]
        events, errors = schema.load(_write_lines(tmp_path / "s.jsonl", lines))
        assert errors == []
        summary = report.summarize_run(events)
        assert "lazy_work" not in summary
        assert "serve.traffic" in summary["spans"]

    def test_main_check_exit_codes(self, tmp_path, capsys):
        path, *_ = _synthetic_run(tmp_path)
        assert report.main([path, "--check"]) == 0
        assert "schema clean" in capsys.readouterr().out
        bad = _write_lines(tmp_path / "bad.jsonl", ["{not json"])
        assert report.main([bad, "--check"]) == 1
        assert "FAIL" in capsys.readouterr().err

    def test_main_json_output(self, tmp_path, capsys):
        path, d, steps, touched = _synthetic_run(tmp_path)
        assert report.main([path, "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["lazy_work"]["work_ratio"] == pytest.approx(touched / (d * steps))
