"""The benchmark's plain references agree with ``repro``'s lazy trainer and
service at a small ``dim`` on the CPU, weight for weight."""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import corpus
from chipbench.drivers import serve, train
from chipbench.references import fobos_dense, ftrl_proximal
from chipbench.references.common import Static
from repro.core import SparseBatch, current_weights, init_state, make_round_fn

SEED = 5


@pytest.mark.parametrize(
    "name, dim, ref", [("medline_bow", 3000, fobos_dense), ("ctr_criteo_hashed", 4096, ftrl_proximal)]
)
def test_reference_rounds_match_the_lazy_trainer(name, dim, ref, small_config):
    c = small_config(name, dim=dim)
    data = corpus.blocks(c, SEED, 3, (32, 8))
    cfg = train.linear_config(c)
    fn = make_round_fn(cfg, "lazy")
    state = init_state(cfg)
    hp = train.reference_hypers(c)
    carry = ref.init(dim, jnp.float32)
    for r in range(3):
        batches = {k: v[r] for k, v in data.items()}
        state, losses = fn(state, SparseBatch(**batches))
        carry, ref_losses = ref._round(Static(hp), jnp.float32, carry, batches)
        np.testing.assert_allclose(np.asarray(losses), np.asarray(ref_losses), rtol=1e-4, atol=1e-6)
    w = np.asarray(current_weights(cfg, state))
    w_ref = np.asarray(ref.read(hp, carry[0], carry[1]) if ref is ftrl_proximal else carry[0])
    assert np.abs(w_ref).max() > 0
    np.testing.assert_allclose(w, w_ref, rtol=1e-4, atol=1e-5 * np.abs(w_ref).max())
    np.testing.assert_allclose(float(state.b), float(carry[-2]), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name, dim", [("medline_bow", 3000), ("ctr_criteo_hashed", 4096)])
def test_reference_readings_match_the_program(name, dim, small_config):
    c = small_config(name, dim=dim)
    prog = train.Program(c, corpus.blocks(c, SEED, 3, (32, 8)))
    readings, kept = prog.first_rounds(3)
    gaps = train.compare(readings, train.reference_rounds(c, kept), c["params"])
    assert all(v < 1e-5 for v in gaps.values()), gaps
    low = train.compare(train.reference_rounds(c, kept, jnp.bfloat16), train.reference_rounds(c, kept), c["params"])
    assert max(low.values()) > 1e-3, low


def test_online_reference_matches_the_service(small_config):
    from repro.serving import LinearService, ServiceConfig

    c = small_config("medline_bow")
    c["serve"]["round_len"] = 16
    blocks = corpus.blocks(c, SEED, 1, (100,))
    idx, val, y = (np.asarray(blocks[k][0]) for k in ("idx", "val", "y"))
    svc = LinearService(
        serve.linear_config(c, "serve"), ServiceConfig(p_max=c["p_max"], micro_batch=8, backend="reference")
    )
    preds = []
    for i in range(100):
        preds.append(svc.predict(SparseBatch(idx=idx[i : i + 1], val=val[i : i + 1], y=y[i : i + 1]))[0])
        svc.submit_learn(idx[i], val[i], float(y[i]))
        svc.poll(now=0.0)
    p_ref, w_ref, _ = fobos_dense.online(
        serve.hypers(c), 3000, {"idx": idx, "val": val, "y": y}
    )
    np.testing.assert_allclose(np.array(preds), np.asarray(p_ref), atol=1e-5)
    np.testing.assert_allclose(svc.current_weights(), np.asarray(w_ref), atol=1e-5)
