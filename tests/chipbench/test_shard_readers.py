"""The feature-mesh cell's readers on a small synthetic four-chip trace and
HLO: only the ``lazy.margin`` ops count toward the psum's time, each
reader takes the mean or the slowest chip as it says, and a program
without the scope reads nothing."""

import pytest

from chipbench import phases, shards, spec, trace, work

HLO = """HloModule jit__lambda, is_scheduled=true, entry_computation_layout={(f32[16,3]{1,0})->f32[16,3]{1,0}}

%fused_gather (param_0: f32[16,3], param_1: s32[4]) -> f32[4,3] {
  %param_0 = f32[16,3]{1,0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  ROOT %gather.1 = f32[4,3]{1,0} gather(%param_0, %param_1), offset_dims={1}, slice_sizes={1,3}, metadata={op_name="jit(<lambda>)/shard_map/while/body/lazy.gather/gather"}
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="while/body/lazy.kernel/lazy.margin/psum"}
  %b = f32[] parameter(1), metadata={op_name="while/body/lazy.kernel/lazy.margin/psum"}
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="lazy.kernel/lazy.margin/add"}
}

%fused_sum (param_0: f32[2,4]) -> f32[2] {
  %param_0 = f32[2,4]{1,0} parameter(0)
  %c0 = f32[] constant(0)
  ROOT %reduce_sum.1 = f32[2]{0} reduce(%param_0, %c0), dimensions={1}, to_apply=%region_add, metadata={op_name="jit(<lambda>)/shard_map/while/body/lazy.kernel/lazy.margin/reduce_sum"}
}

%fused_scatter (param_0: f32[16,3], param_1: s32[4], param_2: f32[4]) -> f32[16,3] {
  %param_0 = f32[16,3]{1,0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  %param_2 = f32[4]{0} parameter(2)
  ROOT %scatter.1 = f32[16,3]{1,0} scatter(%param_0, %param_1, %param_2), to_apply=%region_add, metadata={op_name="jit(<lambda>)/shard_map/while/body/lazy.scatter/scatter-add"}
}

%body (arg: (s32[], f32[16,3])) -> (s32[], f32[16,3]) {
  %arg = (s32[], f32[16,3]{1,0}) parameter(0)
  %gte.1 = f32[16,3]{1,0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[4,3]{1,0} fusion(%gte.1, %idx), kind=kLoop, calls=%fused_gather, metadata={op_name="jit(<lambda>)/shard_map/while/body/lazy.gather/gather"}
  %ftrl_margin.1 = f32[2,4]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/shard_map/while/body/lazy.kernel/pallas_call"}
  %psum.17 = f32[2,4]{1,0} all-reduce(%ftrl_margin.1), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_add, metadata={op_name="jit(<lambda>)/shard_map/while/body/closed_call/lazy.kernel/lazy.margin/psum"}
  %fusion.2 = f32[2]{0} fusion(%psum.17), kind=kLoop, calls=%fused_sum, metadata={op_name="jit(<lambda>)/shard_map/while/body/closed_call/lazy.kernel/lazy.margin/reduce_sum"}
  %ftrl_update.1 = f32[4]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/shard_map/while/body/lazy.kernel/pallas_call"}
  %fusion.3 = f32[16,3]{1,0} fusion(%gte.1, %idx, %ftrl_update.1), kind=kLoop, calls=%fused_scatter, metadata={op_name="jit(<lambda>)/shard_map/while/body/lazy.scatter/scatter-add"}
  ROOT %tuple.1 = (s32[], f32[16,3]{1,0}) tuple(%gte.0, %fusion.3)
}

%cond (arg.1: (s32[], f32[16,3])) -> pred[] {
  %arg.1 = (s32[], f32[16,3]{1,0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%arg.1), index=0
  ROOT %lt.1 = pred[] compare(%gte.2, %c), direction=LT
}

ENTRY %main.1 (p0: f32[16,3]) -> f32[16,3] {
  %p0 = f32[16,3]{1,0} parameter(0)
  %tuple.0 = (s32[], f32[16,3]{1,0}) tuple(%c0, %p0)
  %while.1 = (s32[], f32[16,3]{1,0}) while(%tuple.0), condition=%cond, body=%body
  %gte.3 = f32[16,3]{1,0} get-tuple-element(%while.1), index=1
  ROOT %ftrl_read_rows.1 = f32[16,3]{1,0} custom-call(%gte.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/shard_map/lazy.flush/pallas_call"}
}
"""
UNSCOPED = HLO.replace("lazy.margin/", "")

MODULE = "jit__lambda"
CONFIG = {
    "solver": "ftrl", "mesh": 4, "p_max": 40, "train": {"batch": 8},
    "data": {"dim": 2**30},
}
# ns of each op of one step on chip 0; chip d takes (1 + d / 10) times as
# long for every op, so chip 3 is the slowest
STEP = [
    ("fusion.1", 4_000), ("ftrl_margin.1", 1_000), ("psum.17", 12_000), ("fusion.2", 500),
    ("ftrl_update.1", 800), ("fusion.3", 50_000), ("lt.1", 200),
]
FLUSH_NS = 80_000_000
WINDOW = (0, 200_000_000)
MESH_READERS = (
    "margin_us.train", "shard_step_roofline.train", "shard_flush_roofline.train",
    "shard_idle_share.train",
)


def _scale(d):
    return 1 + d / 10


def _reading(text=HLO, steps=3, rounds=1, with_hlo=True):
    ops = []
    for d in range(4):
        t = 1000
        for _ in range(steps):
            for name, ns in STEP:
                ops.append(trace.Op(name, t, t + int(ns * _scale(d)), MODULE, d))
                t += int(ns * _scale(d))
        ops.append(trace.Op("while.1", 1000, t, MODULE, d))
        ops.append(trace.Op("ftrl_read_rows.1", t, t + int(FLUSH_NS * _scale(d)), MODULE, d))
        t += int(FLUSH_NS * _scale(d))
        ops.append(trace.Op("fusion.9", t, t + 5_000, "jit_take", d))  # another program
    r = trace.Reading(
        trace=trace.Trace(ops=ops, spans=[trace.Span(trace.WINDOW_SPAN, *WINDOW)], window=WINDOW),
        device_kind="TPU v5 lite",
        config=CONFIG,
        steps=steps,
        rounds=rounds,
        module=MODULE,
        ops=trace.classify(text),
    )
    if with_hlo:
        r.hlo = text
    return r


def test_margin_ops_are_those_the_program_scopes_lazy_margin():
    # the reductions' own parameters and roots carry the scope too, but do
    # not run as units on the device
    assert shards.margin_ops(HLO) == {"psum.17", "fusion.2"}
    assert shards.margin_ops(UNSCOPED) == frozenset()
    assert shards.margin_ops("") == frozenset()
    # the four phases still place them in lazy.kernel: no existing reading moves
    table = phases.op_table(HLO)
    assert table["psum.17"][1] == phases.KERNEL and table["fusion.2"][1] == phases.KERNEL


def test_margin_us_averages_the_chips():
    got = spec.reader("margin_us.train")(_reading())
    mean_scale = sum(_scale(d) for d in range(4)) / 4
    assert got == pytest.approx((12_000 + 500) * mean_scale / 1e3, rel=1e-4)


def test_rooflines_take_the_slowest_chip():
    r = _reading()
    bw = 4 * work.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    step_ns = sum(ns for _, ns in STEP) * _scale(3)
    least_step = work.step_bytes(8, 40, 3) / bw
    assert spec.reader("shard_step_roofline.train")(r) == pytest.approx(
        100 * least_step / (step_ns / 1e9), rel=1e-4
    )
    least_flush = work.flush_bytes(2**30, 3) / bw
    assert spec.reader("shard_flush_roofline.train")(r) == pytest.approx(
        100 * least_flush / (FLUSH_NS * _scale(3) / 1e9), rel=1e-4
    )


def test_idle_share_is_the_idlest_chip():
    r = _reading()
    busy = [
        3 * sum(int(ns * _scale(d)) for _, ns in STEP) + int(FLUSH_NS * _scale(d)) + 5_000
        for d in range(4)
    ]
    want = 100 * (1 - min(busy) / (WINDOW[1] - WINDOW[0]))
    assert spec.reader("shard_idle_share.train")(r) == pytest.approx(want)
    assert want > r.idle_share()  # the chips' mean, which a straggler hides in


def test_readers_scale_with_steps_and_rounds():
    one, two = _reading(steps=3), _reading(steps=6, rounds=2)
    m = spec.reader("margin_us.train")
    assert m(two) == pytest.approx(m(one), rel=1e-4)


def test_a_program_without_the_scope_reads_no_margin():
    assert spec.reader("margin_us.train")(_reading(UNSCOPED)) is None
    assert spec.reader("margin_us.train")(_reading(with_hlo=False)) is None


def test_readers_find_nothing_in_an_empty_trace():
    empty = trace.Reading(
        trace=trace.Trace(ops=[], spans=[], window=(0, 10)), device_kind="TPU v5 lite",
        config=CONFIG,
    )
    for m in MESH_READERS:
        assert spec.reader(m)(empty) is None, m
