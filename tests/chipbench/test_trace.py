"""The benchmark's trace reduction on a small synthetic trace: idle share as
a union of intervals, gather/scatter classes from HLO text, the loop-body
versus flush split, and both roofline formulas against hand-counted bytes."""

import pytest

from chipbench import spec, trace, work

HLO = """HloModule jit_round_fn, is_scheduled=true, entry_computation_layout={(f32[10,2]{1,0})->f32[10,2]{1,0}}

%fused_computation.1 (param_0: f32[10,2], param_1: s32[4]) -> f32[4,2] {
  %param_0 = f32[10,2]{1,0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  ROOT %gather.1 = f32[4,2]{1,0} gather(%param_0, %param_1), offset_dims={1}, slice_sizes={1,2}
}

%add_comp (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%fused_computation.2 (param_0: f32[10,2], param_1: s32[4], param_2: f32[4,2]) -> f32[10,2] {
  %param_0 = f32[10,2]{1,0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  %param_2 = f32[4,2]{1,0} parameter(2)
  ROOT %scatter.1 = f32[10,2]{1,0} scatter(%param_0, %param_1, %param_2), to_apply=%add_comp
}

%fused_computation.3 (param_0: f32[10,2]) -> f32[10,2] {
  %param_0 = f32[10,2]{1,0} parameter(0)
  ROOT %multiply.1 = f32[10,2]{0,1:T(2,128)} multiply(%param_0, %param_0)
}

%body (arg: (s32[], f32[10,2])) -> (s32[], f32[10,2]) {
  %arg = (s32[], f32[10,2]{1,0}) parameter(0)
  %gte.1 = f32[10,2]{1,0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[4,2]{1,0} fusion(%gte.1, %idx), kind=kLoop, calls=%fused_computation.1
  %custom-call.1 = f32[4,2]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call"
  %fusion.2 = f32[10,2]{1,0} fusion(%gte.1, %idx, %custom-call.1), kind=kLoop, calls=%fused_computation.2
  ROOT %tuple.1 = (s32[], f32[10,2]{1,0}) tuple(%gte.0, %fusion.2)
}

%cond (arg.1: (s32[], f32[10,2])) -> pred[] {
  %arg.1 = (s32[], f32[10,2]{1,0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%arg.1), index=0
  ROOT %lt.1 = pred[] compare(%gte.2, %c), direction=LT
}

ENTRY %main.1 (p0: f32[10,2], /*index=1*/p1: s32[4]) -> f32[10,2] {
  %p0 = f32[10,2]{1,0} parameter(0)
  %tuple.0 = (s32[], f32[10,2]{1,0}) tuple(%c0, %p0)
  %while.1 = (s32[], f32[10,2]{1,0}) while(%tuple.0), condition=%cond, body=%body
  %gte.3 = f32[10,2]{1,0} get-tuple-element(%while.1), index=1
  ROOT %fusion.3 = f32[10,2]{1,0} fusion(%gte.3), kind=kLoop, calls=%fused_computation.3
}
"""

KIND = "TPU v5 lite"
BW = 819e9


def test_classify_marks_loop_ops_and_gather_scatter_fusions():
    ops = trace.classify(HLO)
    assert trace.module_name(HLO) == "jit_round_fn"
    assert ops["fusion.1"] == {"loop": True, "gs": True}
    assert ops["fusion.2"] == {"loop": True, "gs": True}
    assert ops["custom-call.1"] == {"loop": True, "gs": False}
    assert ops["lt.1"] == {"loop": True, "gs": False}
    assert ops["fusion.3"] == {"loop": False, "gs": False}
    assert "while.1" not in ops  # its event spans the loop's ops, which count themselves
    assert "gather.1" not in ops  # inside a fusion: not a unit on the device


@pytest.mark.parametrize(
    "text, name",
    [
        ("fusion.19", "fusion.19"),
        ("%fusion.19", "fusion.19"),
        (
            "fusion.19 = f32[1024,2]{1,0:T(8,128)S(1)} fusion(f32[260941,2]{1,0:T(8,128)} %copy.34,"
            " s32[1024]{0:T(1024)S(1)} %broadcast_clamp_fusion.4), kind=kCustom,"
            " calls=%fused_computation.clone.clone",
            "fusion.19",
        ),
        ("reshape.285 = f32[2,260941]{1,0:T(2,128)} reshape(f32[521882] %fusion.26)", "reshape.285"),
        ("custom-call.1 = f32[4,2]{1,0} custom-call(%fusion.1)", "custom-call.1"),
    ],
)
def test_op_names_from_tpu_trace_events(text, name):
    # a TPU trace names each op by its whole HLO line; the classes key it by name
    assert trace.op_name(text) == name


@pytest.mark.parametrize(
    "text, base",
    [("jit_round_fn(18136172526847648574)", "jit_round_fn"), ("jit_round_fn", "jit_round_fn"),
     ("jit_take(7)", "jit_take")],
)
def test_module_names_lose_their_fingerprint(text, base):
    assert trace.module_base(text) == base


@pytest.mark.parametrize(
    "intervals, lo, hi, busy",
    [
        ([(0, 10), (5, 15), (20, 30)], 0, 40, 25),
        ([(0, 10), (10, 20)], 0, 20, 20),
        ([(-5, 5), (35, 50)], 0, 40, 10),
        ([(2, 3), (0, 100)], 0, 40, 40),
        ([], 0, 40, 0),
    ],
)
def test_busy_is_a_union_of_intervals(intervals, lo, hi, busy):
    assert trace.union_ns(intervals, lo, hi) == busy
    idle = sum(e - s for s, e in trace.gaps(intervals, lo, hi))
    assert idle == hi - lo - busy


def _reading(config, steps=2, rounds=1):
    op = trace.Op
    ops = [
        # the loop (its event spans its body), two steps of the body, then
        # the flush, then another program
        op("while.1", 1000, 3000, "jit_round_fn", 0),
        op("fusion.1", 1000, 1200, "jit_round_fn", 0),
        op("custom-call.1", 1200, 1500, "jit_round_fn", 0),
        op("fusion.2", 1500, 1900, "jit_round_fn", 0),
        op("lt.1", 1900, 2000, "jit_round_fn", 0),
        op("fusion.1", 2000, 2200, "jit_round_fn", 0),
        op("custom-call.1", 2200, 2500, "jit_round_fn", 0),
        op("fusion.2", 2500, 2900, "jit_round_fn", 0),
        op("lt.1", 2900, 3000, "jit_round_fn", 0),
        op("fusion.3", 3000, 7000, "jit_round_fn", 0),
        op("fusion.7", 8000, 9000, "jit_take", 0),
    ]
    spans = [
        trace.Span(trace.WINDOW_SPAN, 0, 10000),
        trace.Span("chipbench.dispatch", 0, 900),
        trace.Span("chipbench.block", 7000, 8000),
    ]
    tr = trace.Trace(ops=ops, spans=spans, window=(0, 10000))
    return trace.Reading(
        trace=tr,
        device_kind=KIND,
        config=config,
        steps=steps,
        rounds=rounds,
        module="jit_round_fn",
        ops=trace.classify(HLO),
    )


MEDLINE_LIKE = {
    "solver": "fobos",
    "p_max": 128,
    "train": {"batch": 8},
    "data": {"dim": 260941},
}
CTR_LIKE = {"solver": "ftrl", "p_max": 40, "train": {"batch": 8}, "data": {"dim": 2**26}}


def test_bytes_by_hand():
    # gathered (w, psi) rows read and written: 2 * 1024 rows * 2 cols * 4 B,
    # ids and values 1024 * (4 + 4) B, labels 8 * 4 B
    assert work.step_bytes(8, 128, 2) == 16384 + 8192 + 32
    # (w, z, n) rows: 2 * 320 * 3 * 4 B, 320 * 8 B, 8 * 4 B
    assert work.step_bytes(8, 40, 3) == 7680 + 2560 + 32
    assert work.flush_bytes(260941, 2) == 4175056
    assert work.flush_bytes(2**26, 3) == 1610612736
    assert work.state_cols(MEDLINE_LIKE) == 2 and work.state_cols(CTR_LIKE) == 3
    assert work.peaks(KIND)["hbm_bytes_per_s"] == BW
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("config", [MEDLINE_LIKE, CTR_LIKE], ids=["fobos", "ftrl"])
def test_readers_on_a_synthetic_trace(config):
    r = _reading(config)
    read = {m: spec.reader(m) for m in (
        "step_roofline.train", "scatter_share.train", "flush_roofline.train",
        "device_idle_share.train",
    )}
    cols = 3 if config["solver"] == "ftrl" else 2
    body_ns = 2 * (200 + 300 + 400 + 100)  # two steps of the loop
    step_s = body_ns / 1e9 / 2
    least = work.step_bytes(8, config["p_max"], cols) / BW
    assert read["step_roofline.train"](r) == pytest.approx(100 * least / step_s)
    assert read["scatter_share.train"](r) == pytest.approx(100 * (2 * 600) / body_ns)
    flush_least = work.flush_bytes(config["data"]["dim"], cols) / BW
    assert read["flush_roofline.train"](r) == pytest.approx(100 * flush_least / 4000e-9)
    # busy: 1000..7000 and 8000..9000 of a 10000 ns window
    assert read["device_idle_share.train"](r) == pytest.approx(100 * (1 - 7000 / 10000))


def test_readers_find_nothing_to_read():
    empty = trace.Reading(
        trace=trace.Trace(ops=[], spans=[], window=(0, 10)), device_kind=KIND, config=MEDLINE_LIKE
    )
    for m in ("step_roofline.train", "scatter_share.train", "flush_roofline.train",
              "device_idle_share.train", "device_idle_share.serve", "dispatches_per_req.serve"):
        assert spec.reader(m)(empty) is None


def test_dispatches_per_request():
    r = trace.Reading(
        trace=trace.Trace(ops=[], spans=[], window=(0, 10)),
        device_kind=KIND,
        config=MEDLINE_LIKE,
        counters={"predict_calls": 100, "learn_steps": 100, "round_flushes": 1},
        requests=100,
    )
    assert spec.reader("dispatches_per_req.serve")(r) == pytest.approx(2.01)


def test_breakdown_names_ops_and_gaps_by_host_span():
    b = trace.breakdown(_reading(MEDLINE_LIKE))
    assert b["device_ops"][0] == ["jit_round_fn/fusion.3", 4000 / 1e9]
    assert len(b["device_ops"]) <= 10
    gaps = dict((name, s) for name, s in b["idle_gaps"])
    assert gaps["chipbench.dispatch"] == pytest.approx(1000 / 1e9)
    assert gaps["chipbench.block"] == pytest.approx(1000 / 1e9)
    assert max(s for _, s in b["idle_gaps"]) == pytest.approx(1000 / 1e9)
