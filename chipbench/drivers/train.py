"""Training cells: rounds of ``repro.core.make_round_fn(cfg, "lazy")`` over
a corpus made on the device, dispatched back to back.

Set-up makes the corpus, builds the round program and its state, and
drives that same state through the traffic's ``check_rounds`` first rounds
by the window's own call and feed (rounds 0, 1, 2 of the corpus, whose
rows all differ).  Those rounds warm up every program the window runs,
and their readings are what the plain reference is compared with once the
window has closed.  One more round, timed, says how many rounds make the
traffic's ``ahead_seconds`` of device time (at most ``ahead_rounds_max``).
The window then goes on from the round after it, cycling over the corpus,
with that many rounds dispatched ahead of the one the host waits on: the
host waits on the oldest round's losses, never on the round it has just
sent, and a stall of the host shorter than that leaves the device busy.

Compared numbers (each a share, limits in ``chipbench/limits``):

* ``loss_gap``: the largest relative gap of a round's mean loss;
* ``state1_gap``: after the first round, the worst leaf of the state by
  the gap of norms, each against the larger of its own reference norm and
  the median leaf's;
* ``change3_gap``: the same for the parameters' change over the check
  rounds.  Leaves whose reference norm is under a thousandth of the median
  leaf's are left out of a gap.
"""

from __future__ import annotations

import collections
import math
import time

import numpy as np

from chipbench import corpus, trace
from chipbench.harness import CompileWatch, memory_peak_bytes

GAP_FLOOR = 1e-3  # leaves under this share of the median leaf's norm do not count


def linear_config(config: dict, section: str = "train"):
    """The program's ``LinearConfig`` for the configuration's ``train`` or
    ``serve`` section."""
    from repro.core import LinearConfig, ScheduleConfig

    t = config[section]
    return LinearConfig(
        dim=config["data"]["dim"],
        solver=config["solver"],
        lam1=config["hypers"]["lam1"],
        lam2=config["hypers"]["lam2"],
        ftrl_beta=config["hypers"].get("beta", 1.0),
        round_len=t["round_len"],
        schedule=ScheduleConfig(**t["schedule"]),
        backend=config["backend"],
    )


def reference_hypers(config: dict) -> dict:
    return {**config["hypers"], "schedule": config["train"]["schedule"]}


def leaf_gaps(prog: dict, ref: dict) -> float:
    """The worst leaf's gap of norms (see the module's docstring)."""
    med = float(np.median(list(ref.values())))
    worst = 0.0
    for name, r in ref.items():
        if r < GAP_FLOOR * med:
            continue
        worst = max(worst, abs(prog[name] - r) / max(r, med))
    return worst


def compare(prog: list, ref: list, params: list) -> dict:
    """The compared numbers from per-round records ``{"loss", "leaves"}``."""
    loss = max(abs(p["loss"] - r["loss"]) / abs(r["loss"]) for p, r in zip(prog, ref))
    return {
        "loss_gap": loss,
        "state1_gap": leaf_gaps(prog[0]["leaves"], ref[0]["leaves"]),
        "change3_gap": leaf_gaps(
            {k: prog[-1]["leaves"][k] for k in params}, {k: ref[-1]["leaves"][k] for k in params}
        ),
    }


def plant(fault, round_fn, batch: int):
    """The round program with a fault planted underneath (tests only)."""
    import jax
    import jax.numpy as jnp

    if fault is None:
        return round_fn
    if fault == "unchanged":

        def broken(state, batches):
            _, losses = round_fn(jax.tree.map(jnp.copy, state), batches)
            return state, losses

        return broken
    if fault == "half_batch":

        def broken(state, batches):
            return round_fn(state, jax.tree.map(lambda a: a[:, : batch // 2], batches))

        return broken
    raise ValueError(f"no fault {fault!r} for a training cell")


class Program:
    """The round program with its state, driven through the first rounds."""

    def __init__(self, config: dict, data: dict, fault=None):
        import jax
        import jax.numpy as jnp
        from repro.core import SparseBatch, init_state, make_round_fn
        from repro.obs.compile_tracker import CompileTracker

        self.cfg = linear_config(config)
        cols = config["state_columns"]

        @jax.jit
        def take(c, k):
            one = {n: jax.lax.dynamic_index_in_dim(a, k, keepdims=False) for n, a in c.items()}
            return SparseBatch(**one)

        @jax.jit
        def leaves(state):
            out = {name: jnp.linalg.norm(state.wpsi[:, c]) for name, c in cols.items()}
            return {**out, "b": jnp.abs(state.b)}

        self.data, self.take, self.leaves = data, take, leaves
        self.tracker = CompileTracker()
        self.round_fn = self.tracker.register("round", make_round_fn(self.cfg, "lazy"))
        self.tracker.register("take", take)
        self.step = plant(fault, self.round_fn, config["train"]["batch"])
        self.state = init_state(self.cfg)

    def first_rounds(self, n: int) -> tuple:
        """Rounds ``0 .. n-1`` by the window's own call and feed: their
        readings (host floats) and batches (host dicts, for the reference)."""
        import jax
        import jax.numpy as jnp

        kept, readings = [], []
        for r in range(n):
            batches = self.take(self.data, np.int32(r))
            kept.append(batches)
            self.state, losses = self.step(self.state, batches)
            readings.append({"loss": jnp.mean(losses), "leaves": self.leaves(self.state)})
        return jax.tree.map(float, jax.device_get(readings)), [b._asdict() for b in kept]


def reference_rounds(config: dict, kept: list, dtype=None) -> list:
    import importlib

    import jax.numpy as jnp

    ref = importlib.import_module(f"chipbench.references.{config['reference']}")
    return ref.train(reference_hypers(config), config["data"]["dim"], kept, dtype or jnp.float32)


def run(run) -> dict:
    import jax

    config, traffic = run.config, run.traffic
    R, B = config["train"]["round_len"], config["train"]["batch"]
    n_blocks = math.ceil(config["n_examples"] / (R * B))
    checks_n = traffic["check_rounds"]
    watch = CompileWatch()

    data = corpus.blocks(config, run.seed, n_blocks, (R, B))
    prog = Program(config, data, run.fault)
    readings, kept = prog.first_rounds(checks_n)
    step, take, tracker, round_fn = prog.step, prog.take, prog.tracker, prog.round_fn
    state = prog.state
    t = time.monotonic()
    state, losses = step(state, take(data, np.int32(checks_n % n_blocks)))
    losses.block_until_ready()
    round_s = time.monotonic() - t
    ahead = min(traffic["ahead_rounds_max"], max(1, math.ceil(traffic["ahead_seconds"] / round_s)))
    first = checks_n + 1
    setup_s = time.monotonic() - run.t_start
    run.log(
        f"set-up {setup_s:.2f} s: {n_blocks} rounds of {R} x {B} on the device; "
        f"a round takes {round_s:.4f} s, {ahead} dispatched ahead"
    )

    def dispatch(first: int, stop) -> int:
        """Rounds from ``first`` until ``stop(n_sent)``; returns how many."""
        nonlocal state
        inflight = collections.deque()
        k = first
        while not stop(k - first):
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                state, losses = step(state, take(data, np.int32(k % n_blocks)))
            inflight.append(losses)
            if len(inflight) > ahead:
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    inflight.popleft().block_until_ready()
            k += 1
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            jax.block_until_ready((state, list(inflight)))
        return k - first

    out = {"attempted": 0, "failed": 0, "metrics": {}}
    if not run.trace:
        with watch.window(tracker, "train window"):
            t0 = time.monotonic()
            rounds = dispatch(first, lambda n: time.monotonic() - t0 >= run.seconds)
            elapsed = time.monotonic() - t0
        examples = rounds * R * B
        out["attempted"] = examples
        out["metrics"] = {
            "train_ex_per_s": {"value": examples / elapsed, "unit": "examples/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        run.log(f"window {elapsed:.3f} s: {rounds} rounds, {examples} examples")
    else:
        n_trace = traffic["trace_rounds"]
        args = (state, take(data, np.int32(0)))
        hlo = round_fn.lower(*args).compile().as_text()
        with watch.window(tracker, "traced train window"):
            profile = trace.capture(
                run, lambda: dispatch(first, lambda n: n >= n_trace)
            )
        out["attempted"] = n_trace * R * B
        reading = trace.Reading(
            trace=trace.load(profile),
            device_kind=jax.devices()[0].device_kind,
            config=config,
            steps=n_trace * R,
            rounds=n_trace,
            module=trace.module_name(hlo),
            ops=trace.classify(hlo),
        )
        out.update(trace.reduce(run, reading))

    out["memory_peak_bytes"] = memory_peak_bytes(run.cell["chips"])
    for x in jax.tree.leaves((state, data)):
        x.delete()
    del state, data, prog
    out["checks"] = compare(readings, reference_rounds(config, kept), config["params"])
    return out
