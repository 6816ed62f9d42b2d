"""Compile-only checks: the Pallas kernels of the main paths compile for a
described TPU v5e chip at real widths, with no chip attached.

Each case lowers the public kernel wrapper with ``interpret=False`` against
shapes placed on one device of a described ``v5e:2x2`` topology and asserts
that the compiled program holds the Mosaic call (``tpu_custom_call``).  The
TPU compiler refuses here what interpret mode accepts: block shapes that
break the (8, 128) tiling, or more VMEM than a kernel may use.  One case
compiles the whole Medline round program and checks the loop's layout.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
All cases live in this one file so that a single worker loads it.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attn import flash_attention

MEDLINE_DIM = 260_941
HASHED_DIM = 2**24
CRITEO_DIM = 2**26
# stablelm_3b at its published widths: 32 heads of 80
HEADS, HEAD_DIM = 32, 80


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _fused_dp(s):
    bp = _f32(s, 32, 128)
    return ops.dp_fused_step.lower(
        bp, bp, bp, bp, _f32(s, 32), 0.0, 0.1, loss="logistic", use_bias=True, interpret=False
    )


def _fused_ftrl(s):
    bp = _f32(s, 32, 128)
    return ops.ftrl_fused_step.lower(
        bp, bp, bp, _f32(s, 32), 0.0, 0.1, 1.0, 1e-5, 1e-6,
        loss="logistic", use_bias=True, interpret=False,
    )


def _screen(s):
    g = _f32(s, MEDLINE_DIM)
    return ops.screen_mask.lower(g, g, 0.1, 0.2, interpret=False)


def _enet_apply(s):
    w = _f32(s, HASHED_DIM)
    return ops.enet_apply.lower(w, w, w, interpret=False)


def _ftrl_flush(s):
    return ops.ftrl_flush.lower(_f32(s, CRITEO_DIM, 3), 0.1, 1.0, 1.0, 1.0, interpret=False)


def _dp_margin(s):
    bp = _f32(s, 32, 128)
    return ops.dp_margin.lower(bp, bp, bp, bp, interpret=False)


def _flash(s, *, batch, sq, skv, block_q, block_k, offset, grad=False):
    q = jax.ShapeDtypeStruct((batch, HEADS, sq, HEAD_DIM), jnp.bfloat16, sharding=s)
    kv = jax.ShapeDtypeStruct((batch, HEADS, skv, HEAD_DIM), jnp.bfloat16, sharding=s)
    attn = functools.partial(
        flash_attention, causal=True, block_q=block_q, block_k=block_k, interpret=False
    )
    if grad:

        def loss(q, k, v):
            return attn(q, k, v, offset).astype(jnp.float32).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    if offset is None:  # decode: one absolute position per (batch, head) program
        offs = jax.ShapeDtypeStruct((batch * HEADS,), jnp.int32, sharding=s)
        return jax.jit(attn).lower(q, kv, kv, offs)
    return jax.jit(lambda q, k, v: attn(q, k, v, offset)).lower(q, kv, kv)


CASES = {
    "dp_fused_step": _fused_dp,
    "ftrl_fused_step": _fused_ftrl,
    "screen_mask": _screen,
    "enet_apply": _enet_apply,
    "dp_margin": _dp_margin,
    "ftrl_flush": _ftrl_flush,
    "flash_prefill": functools.partial(
        _flash, batch=1, sq=256, skv=256, block_q=128, block_k=128, offset=0
    ),
    "flash_decode": functools.partial(
        _flash, batch=8, sq=1, skv=64, block_q=8, block_k=64, offset=None
    ),
    "flash_prefill_backward": functools.partial(
        _flash, batch=1, sq=256, skv=256, block_q=128, block_k=128, offset=0, grad=True
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    compiled = CASES[case](one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _while_bodies(text: str) -> list:
    """The text of each ``while`` loop's body computation in compiled HLO."""
    names = set(re.findall(r" while\(.*?body=%?([\w.\-]+)", text))
    heads = r"^%?(" + "|".join(map(re.escape, names)) + r") [^\n]*\{$"
    return re.findall(heads + r"\n(.*?)^\}$", text, re.M | re.S) if names else []


def test_medline_round_keeps_the_state_layout(one_chip, monkeypatch):
    """The lazy step at the Medline shape (FoBoS, compiled kernels, rounds of
    2048 x 8 x 128) reads and writes the touched (w, psi) state in the
    chip's own layout: the loop body holds no copy of the whole state."""
    from repro.backend import pallas as pallas_backend
    from repro.core import LinearConfig, ScheduleConfig, SparseBatch, init_state, make_round_fn

    for mod in (ops, pallas_backend):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    cfg = LinearConfig(
        dim=MEDLINE_DIM,
        solver="fobos",
        backend="pallas",
        round_len=2048,
        lam1=2e-4,
        lam2=1e-4,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.02, t0=200.0),
    )
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_state(cfg)),
    )
    R, B, P = cfg.round_len, 8, 128
    batches = SparseBatch(
        idx=jax.ShapeDtypeStruct((R, B, P), jnp.int32, sharding=one_chip),
        val=_f32(one_chip, R, B, P),
        y=_f32(one_chip, R, B),
    )
    text = make_round_fn(cfg, "lazy").lower(state, batches).compile().as_text()
    assert "tpu_custom_call" in text
    bodies = _while_bodies(text)
    assert bodies
    state_copy = re.compile(r"= f32\[%d,2\]\{[^}]*\} copy\(" % MEDLINE_DIM)
    for _, body in bodies:
        assert not state_copy.search(body)


def _outside_loops(text: str) -> str:
    """Compiled HLO text with every ``while`` loop's body taken out."""
    for name, body in _while_bodies(text):
        text = text.replace(body, "")
    return text


def test_criteo_round_flushes_the_state_in_place(one_chip, monkeypatch):
    """The FTRL round at the Criteo shape (d = 2^26, compiled kernels,
    rounds of 2048 x 8 x 40) flushes the donated [d, 3] state in its own
    layout: outside the loop there is no flat read kernel, no [2^18, 256]
    tiling of a column, no [d, 2] copy of (z, n), and less temporary memory
    than half a state (a second whole state is 1 GiB).  The flush is one
    kernel over the state's (3, 128) tiles, a bitcast of the state, written
    over its input."""
    from repro.backend import pallas as pallas_backend
    from repro.core import LinearConfig, ScheduleConfig, SparseBatch, init_state, make_round_fn

    for mod in (ops, pallas_backend):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    cfg = LinearConfig(
        dim=CRITEO_DIM,
        solver="ftrl",
        backend="pallas",
        round_len=2048,
        lam1=1.0,
        lam2=1.0,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.1, t0=200.0),
    )
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_state(cfg)),
    )
    R, B, P = cfg.round_len, 8, 40
    batches = SparseBatch(
        idx=jax.ShapeDtypeStruct((R, B, P), jnp.int32, sharding=one_chip),
        val=_f32(one_chip, R, B, P),
        y=_f32(one_chip, R, B),
    )
    compiled = make_round_fn(cfg, "lazy").lower(state, batches).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and _while_bodies(text)
    outside = _outside_loops(text)
    assert "ftrl_read_rows" not in outside
    assert "f32[262144,256]" not in outside
    zn_copy = re.compile(r"= f32\[%d,2\]\{[^}]*\} slice\(" % CRITEO_DIM)
    assert not zn_copy.search(outside)
    assert re.search(r"input_output_alias=\{ \{0\}: \(0, ", text)
    flush = re.compile(
        r"= f32\[%d,3,128\]\{[^}]*\} custom-call\(.*\bbitcast[.\d]*\).*"
        r"tpu_custom_call.*output_to_operand_aliasing" % (CRITEO_DIM // 128)
    )
    assert len(flush.findall(outside)) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= CRITEO_DIM * 3 * 4, mem
    assert mem.temp_size_in_bytes < 512 * 2**20, mem


CRITEO_TB_DIM = 2**30


def test_criteo_tb_sharded_round_fits_four_chips(topo, one_chip, monkeypatch):
    """The feature-sharded FTRL round at the Criteo 1TB shape (d = 2^30 over
    the four chips of a v5e:2x2, compiled kernels, rounds of 2048 x 8 x 40):
    each device holds one [2^28, 3] slab, the loop body holds exactly one
    all-reduce (the margin psum, scoped ``lazy.margin``), and the program
    fits one chip's 16 GiB.  The state itself is built slab by slab."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.backend import pallas as pallas_backend
    from repro.core import LinearConfig, ScheduleConfig, SparseBatch, init_state, make_round_fn
    from repro.dist import linear as dl

    for mod in (ops, pallas_backend):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    monkeypatch.setattr(
        dl, "feature_mesh",
        lambda cfg: Mesh(np.array(topo.devices[: cfg.mesh]), (cfg.feature_axis,)),
    )
    cfg = LinearConfig(
        dim=CRITEO_TB_DIM,
        solver="ftrl",
        backend="pallas",
        round_len=2048,
        lam1=1.0,
        lam2=1.0,
        schedule=ScheduleConfig(kind="inv_sqrt", eta0=0.1, t0=200.0),
        mesh=4,
    )
    slab = "f32[%d,3]" % (CRITEO_TB_DIM // 4)
    whole = "f32[%d,3]" % CRITEO_TB_DIM
    init_text = jax.jit(lambda: init_state(cfg)).lower().compile().as_text()
    assert slab in init_text and whole not in init_text
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(lambda: init_state(cfg)),
        dl.state_shardings(cfg),
    )
    everywhere = NamedSharding(dl.feature_mesh(cfg), PartitionSpec())
    R, B, P = cfg.round_len, 8, 40
    batches = SparseBatch(
        idx=jax.ShapeDtypeStruct((R, B, P), jnp.int32, sharding=everywhere),
        val=_f32(everywhere, R, B, P),
        y=_f32(everywhere, R, B),
    )
    compiled = make_round_fn(cfg, "lazy").lower(state, batches).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert slab in text and whole not in text
    bodies = [body for _, body in _while_bodies(text)]
    reduce = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .* all-reduce(?:-start)?\(.*$", re.M)
    found = [line for body in bodies for line in reduce.findall(body)]
    assert len(found) == 1, found
    assert "lazy.margin" in found[0]
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert used + mem.temp_size_in_bytes < 16 * 2**30, mem
