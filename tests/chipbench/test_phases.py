"""The lazy step's phases, read from the compiled round program's op
metadata: attribution on exact HLO text (scoped fusions, the layout copies
a TPU compiler inserts around a scatter, a kernel's custom call), both
configurations' programs as the CPU compiles them, and the five phase
readers on a synthetic trace beside the four readers that were there."""

import json

import pytest

from chipbench import phases, spec, trace

# The medline step's shape on a TPU: the state is copied to a row-major
# layout (copy.34) that the gather reads and the scatter-SET writes into,
# copied back (copy.38) and flattened for the scatter-ADD, whose fusion the
# compiler rebuilt without metadata.  The kernel's custom call carries none
# either; its outputs do.
HLO = """HloModule jit_round_fn, is_scheduled=true, entry_computation_layout={(f32[10,2]{1,0})->f32[10,2]{1,0}}

%fused_gather (param_0: f32[10,2], param_1: s32[4]) -> f32[4,2] {
  %param_0 = f32[10,2]{1,0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  ROOT %gather.1 = f32[4,2]{1,0} gather(%param_0, %param_1), offset_dims={1}, slice_sizes={1,2}, metadata={op_name="jit(round_fn)/while/body/closed_call/lazy.gather/gather" stack_frame_id=46}
}

%add_comp (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%fused_set (param_0.1: f32[10,2], param_1.1: s32[4], param_2.1: f32[4,2]) -> f32[10,2] {
  %param_0.1 = f32[10,2]{1,0} parameter(0)
  %param_1.1 = s32[4]{0} parameter(1)
  %param_2.1 = f32[4,2]{1,0} parameter(2)
  ROOT %scatter.1 = f32[10,2]{1,0} scatter(%param_0.1, %param_1.1, %param_2.1), to_apply=%add_comp, metadata={op_name="jit(round_fn)/while/body/closed_call/lazy.scatter/scatter" stack_frame_id=71}
}

%fused_add (param_0.2: f32[20], param_1.2: s32[4], param_2.2: f32[4]) -> f32[20] {
  %param_0.2 = f32[20]{0} parameter(0)
  %param_1.2 = s32[4]{0} parameter(1)
  %param_2.2 = f32[4]{0} parameter(2)
  %reshape.9 = f32[4]{0} reshape(%param_2.2), metadata={op_name="jit(round_fn)/while/body/closed_call/lazy.kernel/reshape"}
  ROOT %scatter.2 = f32[20]{0} scatter(%param_0.2, %param_1.2, %reshape.9), to_apply=%add_comp
}

%fused_flush (param_0.3: f32[10,2]) -> f32[10,2] {
  %param_0.3 = f32[10,2]{1,0} parameter(0)
  ROOT %multiply.1 = f32[10,2]{1,0} multiply(%param_0.3, %param_0.3), metadata={op_name="jit(round_fn)/lazy.flush/mul"}
}

%body (arg: (s32[], f32[10,2])) -> (s32[], f32[10,2]) {
  %arg = (s32[], f32[10,2]{0,1:T(2,128)}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %gte.1 = f32[10,2]{0,1:T(2,128)} get-tuple-element(%arg), index=1
  %idx = s32[4]{0} constant({1, 2, 3, 4})
  %one = s32[] constant(1)
  %copy.34 = f32[10,2]{1,0:T(8,128)} copy(%gte.1)
  %fusion.19 = f32[4,2]{1,0} fusion(%copy.34, %idx), kind=kCustom, calls=%fused_gather, metadata={op_name="jit(round_fn)/while/body/closed_call/lazy.gather/gather" stack_frame_id=46}
  %dp_fused_step.8 = (f32[4,2]{1,0}, f32[4]{0}) custom-call(%fusion.19), custom_call_target="tpu_custom_call"
  %gte.2 = f32[4,2]{1,0} get-tuple-element(%dp_fused_step.8), index=0, metadata={op_name="jit(round_fn)/while/body/closed_call/lazy.kernel/jit(dp_fused_step)/jit(dp_fused_step_kernel)"}
  %gte.3 = f32[4]{0} get-tuple-element(%dp_fused_step.8), index=1, metadata={op_name="jit(round_fn)/while/body/closed_call/lazy.kernel/jit(dp_fused_step)/jit(dp_fused_step_kernel)"}
  %fusion.24 = f32[10,2]{1,0:T(8,128)} fusion(%copy.34, %idx, %gte.2), kind=kCustom, calls=%fused_set, metadata={op_name="jit(round_fn)/while/body/closed_call/lazy.scatter/scatter" stack_frame_id=71}
  %copy.38 = f32[10,2]{0,1:T(2,128)} copy(%fusion.24)
  %reshape.285 = f32[20]{0} reshape(%copy.38)
  %fusion.26 = f32[20]{0} fusion(%reshape.285, %idx, %gte.3), kind=kCustom, calls=%fused_add
  %reshape.284 = f32[10,2]{0,1:T(2,128)} reshape(%fusion.26)
  %add.1 = s32[] add(%gte.0, %one), metadata={op_name="jit(round_fn)/while/body/add"}
  ROOT %tuple.1 = (s32[], f32[10,2]{0,1:T(2,128)}) tuple(%add.1, %reshape.284)
}

%cond (arg.1: (s32[], f32[10,2])) -> pred[] {
  %arg.1 = (s32[], f32[10,2]{0,1:T(2,128)}) parameter(0)
  %gte.4 = s32[] get-tuple-element(%arg.1), index=0
  %c = s32[] constant(2)
  ROOT %lt.1 = pred[] compare(%gte.4, %c), direction=LT
}

ENTRY %main.1 (p0: f32[10,2]) -> f32[10,2] {
  %p0 = f32[10,2]{0,1:T(2,128)} parameter(0)
  %c0 = s32[] constant(0)
  %tuple.0 = (s32[], f32[10,2]{0,1:T(2,128)}) tuple(%c0, %p0)
  %while.1 = (s32[], f32[10,2]{0,1:T(2,128)}) while(%tuple.0), condition=%cond, body=%body
  %gte.9 = f32[10,2]{0,1:T(2,128)} get-tuple-element(%while.1), index=1
  ROOT %fusion.3 = f32[10,2]{0,1:T(2,128)} fusion(%gte.9), kind=kLoop, calls=%fused_flush, metadata={op_name="jit(round_fn)/lazy.flush/mul" stack_frame_id=9}
}
"""

# the same program as an older tree compiles it: no scopes
UNSCOPED = HLO.replace("lazy.gather/", "").replace("lazy.kernel/", "").replace(
    "lazy.scatter/", ""
).replace("lazy.flush/", "")

G, K, S, F = phases.GATHER, phases.KERNEL, phases.SCATTER, phases.FLUSH


@pytest.mark.parametrize(
    "op, phase",
    [
        ("fusion.19", G),  # a scoped fusion
        ("copy.34", S),  # unscoped copy read by the gather, then written by the scatter
        ("dp_fused_step.8", K),  # a kernel's custom call: its scoped outputs
        ("fusion.24", S),
        ("copy.38", S),  # unscoped copy after the scatter, into the flat scatter
        ("reshape.285", S),
        ("fusion.26", S),  # rebuilt scatter: the phase of the buffer it writes into
        ("reshape.284", S),  # after it: read by nothing scoped, reads the scatter
        ("add.1", None),  # the loop counter
        ("lt.1", None),
        ("fusion.3", F),
    ],
)
def test_phase_of_each_op_from_exact_hlo_text(op, phase):
    assert phases.op_table(HLO)[op][1] == phase


def test_table_holds_the_ops_that_run_as_units():
    table = phases.op_table(HLO)
    assert set(table) - {"while.1"} == set(trace.classify(HLO))
    assert table["while.1"][0] == "while" and table["dp_fused_step.8"][0] == "custom-call"
    assert "gather.1" not in table and "scatter.2" not in table  # inside fusions


@pytest.mark.parametrize(
    "op_name, phase",
    [
        ("jit(round_fn)/while/body/closed_call/lazy.scatter/scatter", S),
        ("lazy.scatter/reduce_sum", S),
        ("jit(round_fn)/lazy.flush/jit(lazy.gather)/add", G),  # the innermost
        ("jit(round_fn)/while/body/lazy.kernelish/mul", None),
        ("jit(round_fn)/while/body/add", None),
    ],
)
def test_scope_is_the_innermost_phase_name(op_name, phase):
    assert phases.scope_of(op_name) == phase


def _cpu_reading(config):
    """The compiled round program of a configuration, as the CPU compiles
    it, and a reading that names that program without carrying its text."""
    text = phases._compile_round_program(json.dumps(config, sort_keys=True))
    reading = trace.Reading(
        trace=trace.Trace(ops=[], spans=[], window=(0, 1)),
        device_kind="cpu",
        config=config,
        module=trace.module_name(text),
        ops=trace.classify(text),
    )
    return text, reading


@pytest.mark.parametrize("name", ["medline_bow", "ctr_criteo_hashed"])
def test_round_programs_name_all_four_phases(small_config, name):
    text, reading = _cpu_reading(small_config(name))
    assert phases.round_hlo(reading) == text  # compiled again, the same ops
    table = phases.op_table(text)
    assert {p for _, p in table.values()} >= set(phases.PHASES)
    classes = trace.classify(text)
    unphased = [op for op, (_, p) in table.items() if p is None]
    assert [op for op in unphased if classes.get(op, {}).get("gs")] == []


def test_a_program_compiled_again_that_differs_is_not_read(small_config):
    _, reading = _cpu_reading(small_config("medline_bow"))
    reading.ops = {**reading.ops, "fusion.9999": {"loop": True, "gs": False}}
    assert phases.round_hlo(reading) == ""


PHASE_READERS = (
    "gather_us.train", "kernel_us.train", "scatter_us.train", "flush_ms.train",
    "unphased_share.train",
)
OLD_READERS = (
    "step_roofline.train", "scatter_share.train", "flush_roofline.train",
    "device_idle_share.train",
)
CONFIG = {"solver": "fobos", "p_max": 128, "train": {"batch": 8}, "data": {"dim": 260941}}
STEP = [  # (op, ns) of one step, in the order they run
    ("copy.34", 212_000), ("fusion.19", 11_000), ("dp_fused_step.8", 600),
    ("fusion.24", 77_000), ("copy.38", 179_000), ("reshape.285", 6_000),
    ("fusion.26", 9_000), ("reshape.284", 3_000), ("add.1", 200), ("lt.1", 300),
]


def _reading(text=HLO, steps=2, rounds=1, with_hlo=True):
    ops, t = [], 1000
    loop_start = t
    for _ in range(steps):
        for name, ns in STEP:
            ops.append(trace.Op(name, t, t + ns, "jit_round_fn", 0))
            t += ns
    ops.append(trace.Op("while.1", loop_start, t, "jit_round_fn", 0))
    ops.append(trace.Op("fusion.3", t, t + 3_800_000, "jit_round_fn", 0))
    t += 3_800_000
    ops.append(trace.Op("fusion.7", t, t + 50_000, "jit_take", 0))  # another program
    window = (0, t + 100_000)
    r = trace.Reading(
        trace=trace.Trace(ops=ops, spans=[trace.Span(trace.WINDOW_SPAN, *window)], window=window),
        device_kind="TPU v5 lite",
        config=CONFIG,
        steps=steps,
        rounds=rounds,
        module="jit_round_fn",
        ops=trace.classify(text),
    )
    if with_hlo:
        r.hlo = text
    return r


def test_phase_readers_on_a_synthetic_trace():
    r = _reading()
    got = {m: spec.reader(m)(r) for m in PHASE_READERS}
    assert got["gather_us.train"] == pytest.approx(11.0)
    assert got["kernel_us.train"] == pytest.approx(0.6)
    assert got["scatter_us.train"] == pytest.approx(212 + 77 + 179 + 6 + 9 + 3)
    assert got["flush_ms.train"] == pytest.approx(3.8)
    step_ns = sum(ns for _, ns in STEP)
    total = 2 * step_ns + 3_800_000
    assert got["unphased_share.train"] == pytest.approx(100 * 2 * 500 / total)
    # the phases and the unphased time add up to the round program's time
    unphased = got["unphased_share.train"] / 100 * total
    phased = (got["gather_us.train"] + got["kernel_us.train"] + got["scatter_us.train"]) * 1e3
    assert phased * r.steps + got["flush_ms.train"] * 1e6 * r.rounds + unphased == pytest.approx(
        total
    )


def test_phase_readers_scale_with_steps_and_rounds():
    r = _reading(steps=4, rounds=2)
    assert spec.reader("scatter_us.train")(r) == pytest.approx(486.0)
    assert spec.reader("flush_ms.train")(r) == pytest.approx(1.9)


def test_phase_readers_find_nothing_in_a_program_without_scopes():
    r = _reading(UNSCOPED)
    for m in PHASE_READERS:
        assert spec.reader(m)(r) is None, m
    empty = trace.Reading(
        trace=trace.Trace(ops=[], spans=[], window=(0, 10)), device_kind="cpu", config=CONFIG
    )
    for m in PHASE_READERS:
        assert spec.reader(m)(empty) is None, m


def test_old_readers_read_the_same_with_the_program_text_and_the_phases():
    plain = _reading(with_hlo=False)
    before = {m: spec.reader(m)(plain) for m in OLD_READERS}
    assert all(v is not None for v in before.values())
    r = _reading()
    for m in PHASE_READERS:
        spec.reader(m)(r)
    assert {m: spec.reader(m)(r) for m in OLD_READERS} == before
