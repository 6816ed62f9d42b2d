"""The control, at a size a test run holds: the plain reference computed in
bfloat16, put in the program's place, fails the cell's own limits (on the
chip it is read at the cell's size by ``chipbench/calibrate.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import corpus, harness, spec
from chipbench.drivers import serve, train
from chipbench.references import fobos_dense


@pytest.mark.parametrize("workload, name", [("medline.train", "medline_bow"), ("ctr.train", "ctr_criteo_hashed")])
def test_bf16_reference_fails_a_train_cell(workload, name, small_config):
    c = small_config(name, round_len=256)
    data = corpus.blocks(c, 11, 3, (c["train"]["round_len"], 8))
    kept = [{k: v[r] for k, v in data.items()} for r in range(3)]
    ref = train.reference_rounds(c, kept)
    low = train.compare(train.reference_rounds(c, kept, jnp.bfloat16), ref, c["params"])
    correct, checks = harness.verdict(low, spec.limits(workload))
    assert not correct, checks


def test_bf16_reference_fails_the_serve_cell(small_config):
    c = small_config("medline_bow", round_len=256)
    b = corpus.blocks(c, 11, 1, (512,))
    feed = {k: np.asarray(b[k][0]) for k in ("idx", "val", "y")}
    hp = serve.hypers(c)
    p16, w16, _ = fobos_dense.online(hp, 3000, feed, t=2049, dtype=jnp.bfloat16)
    low = serve.compare(c, {"preds": p16, "w": w16, "feed": feed, "t": 2049})
    correct, checks = harness.verdict(low, {"pred_gap": {"limit": 1e-4}, "w_gap": {"limit": 1e-4}})
    assert not correct, checks
