"""Activation-sharding context.

Model code annotates activations with *logical* axis names::

    q = shard(q, "batch", None, "heads_act", None)

and stays oblivious to meshes.  ``activate(mesh, rules)`` installs the
translation table for the duration of a trace; ``shard`` then applies
``jax.lax.with_sharding_constraint`` with the resolved PartitionSpec.  With
no active context ``shard`` is the identity — the same model code runs
unsharded on a single CPU device (every smoke test does exactly this).

The context is consulted at TRACE time, not at run time: jit functions must
be traced (lowered) inside ``activate`` for the constraints to be baked in.
``launch/dryrun.py`` and ``launch/train.py`` both do this; a function traced
outside any context simply contains no constraints.

Unlike jit argument shardings, a with_sharding_constraint may shard a
non-divisible dim (GSPMD pads), which the activation rules exploit for odd
head/vocab counts — see ``dist.sharding`` for the rule-gating policy.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec

Rules = Dict[str, Any]  # logical axis name -> mesh axis (str | tuple | None)

# innermost-last stack of (mesh, rules); plain module state is fine — jax
# traces on the calling thread, and nested activations (e.g. the stripped-pod
# rules inside the compress region) push/pop in LIFO order.
_STACK: list = []


@contextlib.contextmanager
def activate(mesh, rules: Rules):
    """Install ``(mesh, rules)`` as the active sharding context."""
    _STACK.append((mesh, rules))
    try:
        yield
    finally:
        _STACK.pop()


def _current() -> Optional[Tuple[Any, Rules]]:
    """The innermost active ``(mesh, rules)``, or None."""
    return _STACK[-1] if _STACK else None


def resolve(rules: Rules, name: Optional[str]):
    """Logical axis name -> mesh axis (str | tuple | None).  Unknown names
    are an error: the logical vocabulary lives in models/params.py and the
    rule table must cover it."""
    if name is None:
        return None
    try:
        return rules[name]
    except KeyError:
        raise KeyError(
            f"unknown logical axis {name!r}; rule table knows {sorted(rules)}"
        ) from None


def shard(x, *logical_axes: Optional[str]):
    """Constrain ``x`` (one logical name or None per dim) under the active
    context; identity when no context is active."""
    ctx = _current()
    if ctx is None:
        return x
    mesh, rules = ctx
    if len(logical_axes) != x.ndim:
        raise ValueError(
            f"shard(): got {len(logical_axes)} axis names for rank-{x.ndim} array"
        )
    spec = PartitionSpec(*(resolve(rules, n) for n in logical_axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def manual_shard_map(fn, mesh, in_specs, out_specs, *, manual_axes):
    """Partially-manual shard_map: the axes in ``manual_axes`` become manual
    (collectives by name), every other mesh axis stays automatic so GSPMD
    partitions the body exactly like the surrounding jit region.  Used by
    the cross-pod gradient compression (dist/compress.py), where only "pod"
    is manual, and by the feature-sharded linear paths (dist/linear.py)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(manual_axes), check_vma=False,
    )
