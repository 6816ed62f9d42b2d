"""Every round-program factory names the lazy step's four phases.

The scopes live in the solvers and the flush entries, so the single-config
round, its instrumented twin, the vmapped sweep and the feature-sharded
round all compile with ``lazy.gather``, ``lazy.kernel``, ``lazy.scatter``
and ``lazy.flush`` in their ops' metadata — what a profile of the chip
splits the step by.  Scopes are metadata only: the compiled ops are the
same without them.
"""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import LinearConfig, SparseBatch, init_state, make_round_fn, phases
from repro.sweeps.batched_trainer import init_batched_state, make_batched_round_fn

DIM, R, B, P = 64, 4, 2, 3


def _batches(seed=0):
    rng = np.random.RandomState(seed)
    return SparseBatch(
        idx=jnp.asarray(rng.randint(0, DIM, size=(R, B, P)), jnp.int32),
        val=jnp.asarray(rng.uniform(-1, 1, size=(R, B, P)), jnp.float32),
        y=jnp.asarray(rng.uniform(size=(R, B)) > 0.5, jnp.float32),
    )


def _cfg(solver):
    return LinearConfig(dim=DIM, solver=solver, round_len=R, lam1=1e-3, lam2=1e-4)


def _core(cfg):
    return make_round_fn(cfg, "lazy"), (init_state(cfg), _batches())


def _instrumented(cfg):
    return make_round_fn(cfg, "lazy", metrics=True), (obs.init_obs(cfg), _batches())


def _sweep(cfg):
    hp = cfg.hypers()
    bhp = jax.tree.map(lambda x: jnp.full((2,), x, jnp.float32), hp)
    return make_batched_round_fn(cfg), (init_batched_state(cfg, 2), bhp, _batches())


def _sharded(cfg):
    cfg = dataclasses.replace(cfg, mesh=1)
    return make_round_fn(cfg, "lazy"), (init_state(cfg), _batches())


FACTORIES = {"core": _core, "instrumented": _instrumented, "sweep": _sweep, "sharded": _sharded}


def _scopes(fn, args) -> set:
    text = fn.lower(*args).compile().as_text()
    return set(re.findall(r"lazy\.(?:gather|kernel|scatter|flush)", text))


@pytest.mark.parametrize("solver", ["fobos", "ftrl"])
@pytest.mark.parametrize("factory", sorted(FACTORIES))
def test_round_program_names_the_four_phases(factory, solver):
    fn, args = FACTORIES[factory](_cfg(solver))
    assert _scopes(fn, args) == set(phases.PHASES)


META = re.compile(r', metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:^[ \t]+\S.*\n|^\d.*\n)*", re.M
)


def _ops_only(fn, args) -> str:
    """The compiled program without its metadata and source tables."""
    return TABLES.sub("", META.sub("", fn.lower(*args).compile().as_text()))


@pytest.mark.parametrize("solver", ["fobos", "ftrl"])
def test_scopes_change_only_metadata(solver, monkeypatch):
    """The round compiled with and without the scopes is the same program,
    op for op, once metadata and source locations are set aside."""
    scoped = _ops_only(*_core(_cfg(solver)))
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    fn, args = _core(_cfg(solver))
    assert _scopes(fn, args) == set()
    assert _ops_only(fn, args) == scoped
