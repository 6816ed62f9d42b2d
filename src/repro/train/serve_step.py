"""Serving steps: batched prefill and single-token decode with greedy or
temperature sampling.  The decode path is what the decode_* / long_* shape
cells lower (one new token against a seq_len-deep cache)."""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.models import transformer
from repro.models.api import ModelFns


def make_prefill_step(cfg: ArchConfig, model: ModelFns):
    def prefill_step(params, batch):
        last_logits, cache = model.prefill_fn(params, batch)
        next_tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        return next_tok, last_logits, cache

    return prefill_step


def make_serve_step(cfg: ArchConfig, model: ModelFns, *, temperature: float = 0.0):
    def serve_step(params, cache, token, pos, key: Optional[jax.Array] = None):
        logits, cache = model.decode_fn(params, cache, token, pos)
        if temperature > 0.0 and key is not None:
            next_tok = jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)
        else:
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, cache

    return serve_step


def generate(cfg: ArchConfig, model: ModelFns, params, batch, n_new: int,
             *, temperature: float = 0.0, seed: int = 0,
             timings: Optional[Dict[str, float]] = None,
             logits: Optional[List[jax.Array]] = None):
    """Convenience loop (examples / tests / the engine's parity baseline):
    prefill then decode n_new tokens — greedy, or sampled with a
    split-per-step key when temperature > 0.  Python loop — fine at example
    scale.  Pass a dict as ``timings`` to receive block_until_ready-accurate
    "prefill_s" / "decode_s" (launch/serve.py's static driver reads them),
    and a list as ``logits`` to receive the [B, V] logits each token was
    picked from."""
    prefill = jax.jit(make_prefill_step(cfg, model))
    step = jax.jit(make_serve_step(cfg, model, temperature=temperature), donate_argnums=1)
    t0 = time.monotonic()
    tok, last_logits, cache = prefill(params, batch)
    if timings is not None:
        jax.block_until_ready(tok)
        timings["prefill_s"] = time.monotonic() - t0
    P = cfg.n_patches if cfg.n_patches else 0
    pos = batch["tokens"].shape[1] + P
    # decode writes k/v at pos..pos+n_new-2: grow past the prefill headroom
    # or the scatter silently drops out-of-bounds writes (dense-KV families)
    cache = transformer.grow_cache(cache, pos + n_new)
    key = jax.random.PRNGKey(seed) if temperature > 0.0 else None
    if key is not None:  # resample the prefill token (argmax by default)
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, last_logits / temperature, axis=-1).astype(jnp.int32)
    out = [tok]
    if logits is not None:
        logits.append(last_logits)
    t0 = time.monotonic()
    for k in range(n_new - 1):
        sub = None
        if key is not None:
            key, sub = jax.random.split(key)
        tok, step_logits, cache = step(params, cache, tok, jnp.asarray(pos + k, jnp.int32), sub)
        out.append(tok)
        if logits is not None:
            logits.append(step_logits)
    if timings is not None:
        jax.block_until_ready(tok)
        timings["decode_s"] = time.monotonic() - t0
    return jnp.stack(out, axis=1)  # [B, n_new]
