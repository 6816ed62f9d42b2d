"""Serving cells: online progressive validation through ``LinearService``.

Set-up makes the request stream from the seed (Poisson arrivals at the
traffic's fixed ``rate_per_s`` over the window, one example each, made on
the device and copied to the host), builds the service, warms every
program it can run (each predict and learn bucket, and one whole round so
that the round flush runs), then installs zero weights with
``swap_weights``.

The window is an open loop on the host's clock.  Each request is handled
when it is due, or as soon as the loop is free: ``predict`` on its
document, then ``submit_learn`` of its label and ``poll``.  A prediction's
latency runs from the request's due time to the return of ``predict``; a
label's learn lag from the same due time to the return of the ``poll``
that trained it.  Every request due in the window is served, however late.

Compared numbers, against ``online`` of the configuration's reference,
which replays the same stream one example at a time:

* ``pred_gap``: the largest gap of a returned probability;
* ``w_gap``: the largest gap of a final weight, over the largest
  reference weight.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from chipbench import corpus, trace
from chipbench.drivers.train import linear_config
from chipbench.harness import CompileWatch, memory_peak_bytes

BLOCK = 4096  # examples per generated block


def arrivals(kind: str, seed: int, rate: float, seconds: float) -> np.ndarray:
    """Arrival times in ``[0, seconds)``: a Poisson process of ``rate``."""
    if kind != "poisson":
        raise ValueError(f"no arrival process {kind!r}")
    rng = np.random.default_rng(seed)
    n = int(rate * seconds * 1.5) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    if t[-1] < seconds:
        raise RuntimeError("too few arrivals drawn")
    return t[t < seconds]


def percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q))


def plant(fault, svc) -> None:
    """A fault planted in the service underneath (tests only)."""
    if fault is None:
        return
    import jax
    import jax.numpy as jnp

    if fault == "unchanged":
        step = svc._step
        jax.tree.map(jnp.copy, svc.state)  # compiled here, not in the window

        def broken(state, batch):
            _, loss = step(jax.tree.map(jnp.copy, state), batch)
            return state, loss

        svc._step = broken
    elif fault == "altered_answer":
        predict = svc._predict
        calls = [0]
        jnp.zeros((1,), jnp.float32) + 0.1  # compiled here, not in the window

        def broken(state, batch):
            calls[0] += 1
            p = predict(state, batch)
            return p + 0.1 if calls[0] == 3 else p

        svc._predict = broken
    else:
        raise ValueError(f"no fault {fault!r} for a serving cell")


def run(run) -> dict:
    import jax
    from repro.core import SparseBatch
    from repro.serving import LinearService, ServiceConfig

    config, traffic = run.config, run.traffic
    cfg = linear_config(config, "serve")
    s = config["serve"]
    p_max, dim = config["p_max"], config["data"]["dim"]
    seconds = traffic["trace_seconds"] if run.trace else run.seconds
    rate = traffic["rate_per_s"]
    watch = CompileWatch()

    due = arrivals(traffic["arrivals"], run.seed, rate, seconds)
    n = len(due)
    # a fixed number of blocks for the cell, whatever the seed draws
    n_blocks = math.ceil((rate * seconds * 1.2 + 256) / BLOCK)
    if n + 2 * s["round_len"] > n_blocks * BLOCK:
        n_blocks = math.ceil((n + 2 * s["round_len"]) / BLOCK)
    stream = corpus.blocks(config, run.seed, n_blocks, (BLOCK,))
    idx, val, y = (np.asarray(stream[k]).reshape(-1, *stream[k].shape[2:]) for k in ("idx", "val", "y"))
    del stream

    svc = LinearService(
        cfg,
        ServiceConfig(
            p_max=p_max, micro_batch=s["micro_batch"], max_delay=s["max_delay"],
            backend=config["backend"], solver=config["solver"],
        ),
    )
    # warm-up on the stream's tail: every bucket of learn and predict, and
    # a whole round of learn steps so that the round flush runs
    warm = n_blocks * BLOCK - 2 * s["round_len"]
    k = warm
    for b in svc.buckets:
        svc.predict(SparseBatch(idx=idx[k : k + b], val=val[k : k + b], y=y[k : k + b]))
    for m in range(s["round_len"] + 1):
        b = svc.buckets[m % len(svc.buckets)]
        svc.learn(SparseBatch(idx=idx[k : k + b], val=val[k : k + b], y=y[k : k + b]))
        k = k + b if k + 2 * b <= n_blocks * BLOCK else warm
    svc.swap_weights(w=np.zeros(dim, np.float32), b=0.0)
    t_first = int(svc.state.t)
    before = _counters(svc)
    setup_s = time.monotonic() - run.t_start
    run.log(f"set-up {setup_s:.2f} s: {n} requests at {rate} /s over {seconds} s")

    late = np.zeros(n)
    t_pred = np.zeros(n)
    t_learn = np.zeros(n)
    preds = np.zeros(n, np.float32)
    plant(run.fault, svc)

    def window():
        t0 = time.monotonic()
        when = t0 + due
        for i in range(n):
            now = time.monotonic()
            if now < when[i]:
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    if when[i] - now > 2e-4:
                        time.sleep(when[i] - now - 2e-4)
                    while time.monotonic() < when[i]:
                        pass
            late[i] = time.monotonic() - when[i]
            with jax.profiler.TraceAnnotation("chipbench.predict"):
                preds[i] = svc.predict(
                    SparseBatch(idx=idx[i : i + 1], val=val[i : i + 1], y=y[i : i + 1])
                )[0]
            t_pred[i] = time.monotonic() - when[i]
            with jax.profiler.TraceAnnotation("chipbench.learn"):
                svc.submit_learn(idx[i], val[i], float(y[i]), arrival=due[i])
                svc.poll(now=time.monotonic() - t0)
            t_learn[i] = time.monotonic() - when[i]
        return time.monotonic() - t0

    gc.collect()
    out = {"attempted": n, "failed": 0, "metrics": {}, "lateness": late}
    with watch.window(svc.compiles, "serve window"):
        if run.trace:
            profile = trace.capture(run, window)
        else:
            elapsed = window()
    after = _counters(svc)
    run.log(
        "generator lateness ms: p50 %.4f p99 %.4f max %.4f"
        % (1e3 * np.median(late), 1e3 * percentile(late, 99), 1e3 * late.max())
    )
    delta = {c: after[c] - before[c] for c in after}
    if run.trace:
        reading = trace.Reading(
            trace=trace.load(profile),
            device_kind=jax.devices()[0].device_kind,
            config=config,
            counters=delta,
            requests=n,
        )
        out.update(trace.reduce(run, reading))
    else:
        out["metrics"] = {
            "predict_p50_ms": {"value": 1e3 * float(np.median(t_pred)), "unit": "ms"},
            "predict_p99_ms": {"value": 1e3 * percentile(t_pred, 99), "unit": "ms"},
            "learn_lag_p99_ms": {"value": 1e3 * percentile(t_learn, 99), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        run.log(f"window {elapsed:.3f} s, {n} requests, counters {delta}")

    learned, steps = delta["learn_examples"], delta["learn_steps"]
    out["memory_peak_bytes"] = memory_peak_bytes(run.cell["chips"])
    w_prog = svc.current_weights()
    del svc
    if learned != n or steps != n:
        # the reference replays one learn step per request; anything else
        # cannot be replayed, and is not correct
        run.log(f"{learned} examples learned in {steps} steps for {n} requests")
        out["failed"] = n - learned
        out["checks"] = {"pred_gap": math.inf, "w_gap": math.inf}
        return out
    out["record"] = {"preds": preds, "w": w_prog, "feed": {"idx": idx[:n], "val": val[:n], "y": y[:n]}, "t": t_first}
    out["checks"] = compare(config, out["record"])
    return out


def reference(config: dict):
    import importlib

    return importlib.import_module(f"chipbench.references.{config['reference']}")


def hypers(config: dict) -> dict:
    return {**config["hypers"], "schedule": config["serve"]["schedule"]}


def compare(config: dict, record: dict) -> dict:
    """The compared numbers: the answers in ``record`` against the float32
    reference's replay of the same stream."""
    p_ref, w_ref, _ = reference(config).online(
        hypers(config), config["data"]["dim"], record["feed"], t=record["t"]
    )
    p_ref, w_ref = np.asarray(p_ref, np.float64), np.asarray(w_ref, np.float64)
    return {
        "pred_gap": float(np.max(np.abs(np.asarray(record["preds"], np.float64) - p_ref))),
        "w_gap": float(np.max(np.abs(np.asarray(record["w"], np.float64) - w_ref)) / np.max(np.abs(w_ref))),
    }


def _counters(svc) -> dict:
    snap = svc.metrics.snapshot()
    c = snap["counters"]
    return {
        "predict_calls": snap.get("latency_predict", {}).get("count", 0),
        "learn_steps": c.get("learn_steps", 0),
        "learn_examples": c.get("learn_examples", 0),
        "round_flushes": c.get("round_flushes", 0),
    }
