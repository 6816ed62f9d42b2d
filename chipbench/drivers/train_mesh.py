"""Training cells on a feature mesh: rounds of
``repro.core.make_round_fn(cfg, "lazy")`` with ``cfg.mesh`` chips sharing
the ``[d, cols]`` state, each chip owning one contiguous slab of the ids
(``repro.dist.linear``).

Set-up, the check rounds, the window, the planted faults and the compared
numbers are those of ``chipbench/drivers/train.py`` (its functions, or a
copy of its steps), except that

* the program's ``LinearConfig`` takes the configuration's ``mesh`` and
  ``shard_margin``, so ``init_state`` builds the state slab by slab and the
  round program is one per-device program over the mesh;
* ``corpus.blocks`` makes the corpus on every chip of the mesh at once,
  replicated: each shard walks the whole batch and keeps the ids it owns;
* a traced run hands the readers the compiled per-device round program
  (``reading.hlo``), whose ops the trace names on every chip.

Its calibration readings come from ``chipbench/calibrate_mesh.py``.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time

import numpy as np

from chipbench import corpus, trace
from chipbench.drivers import train
from chipbench.drivers.train import compare, plant, reference_rounds
from chipbench.harness import CompileWatch, memory_peak_bytes


def linear_config(config: dict):
    """The program's ``LinearConfig``: the ``train`` section over the mesh."""
    return dataclasses.replace(
        train.linear_config(config), mesh=config["mesh"], shard_margin=config["shard_margin"]
    )


def blocks(config: dict, seed: int, n_blocks: int, shape: tuple):
    """``corpus.blocks``, made by every chip of the feature mesh at once
    and left there replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.dist import linear as dl

    everywhere = NamedSharding(dl.feature_mesh(linear_config(config)), PartitionSpec())
    return jax.jit(
        lambda: corpus.blocks(config, seed, n_blocks, shape), out_shardings=everywhere
    )()


class Program(train.Program):
    """``train.Program`` with the mesh configuration: the round program
    over the mesh and its state, built slab by slab."""

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

    def delete(self) -> None:
        """Free the state's slabs (another program's state may follow)."""
        import jax

        for x in jax.tree.leaves(self.state):
            x.delete()


def run(run) -> dict:
    import jax

    config, traffic = run.config, run.traffic
    R, B = config["train"]["round_len"], config["train"]["batch"]
    n_blocks = math.ceil(config["n_examples"] / (R * B))
    checks_n = traffic["check_rounds"]
    watch = CompileWatch()

    data = blocks(config, run.seed, n_blocks, (R, B))
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
        f"set-up {setup_s:.2f} s: {n_blocks} rounds of {R} x {B} on each of {config['mesh']} "
        f"chips; a round takes {round_s:.4f} s, {ahead} dispatched ahead"
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
        hlo = round_fn.lower(state, take(data, np.int32(0))).compile().as_text()
        with watch.window(tracker, "traced train window"):
            profile = trace.capture(run, lambda: dispatch(first, lambda n: n >= n_trace))
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
        reading.hlo = hlo  # the per-device program: chipbench/phases.py reads it first
        out.update(trace.reduce(run, reading))

    out["memory_peak_bytes"] = memory_peak_bytes(run.cell["chips"])
    for x in jax.tree.leaves((state, data)):
        x.delete()
    del state, data, prog
    out["checks"] = compare(readings, reference_rounds(config, kept), config["params"])
    return out
