"""The benchmark's device-side generators, on the CPU at a small ``dim``:
Medline-shaped documents against ``repro.data.synthetic_bow``'s statistics,
Criteo-shaped rows against the Criteo layout."""

import copy
import math

import numpy as np
import pytest

from chipbench import corpus, spec
from chipbench.generators import bow_zipf, ctr_hashed
from repro.data import BowConfig, SyntheticBow

N = 8192
SEED = 2**31 + 77  # wider than 32 signed bits, as a caller's seed may be


def _bow_config(dim=4000):
    c = copy.deepcopy(spec.config("medline_bow"))
    c["data"].update(dim=dim, informative_pool=1000)
    return c


def _flat(blocks):
    return [np.asarray(blocks[k]).reshape(-1, *blocks[k].shape[2:]) for k in ("idx", "val", "y")]


@pytest.fixture(scope="module")
def bow():
    return _flat(corpus.blocks(_bow_config(), SEED, 2, (N // 2,)))


@pytest.fixture(scope="module")
def host_bow():
    c = _bow_config()["data"]
    b = SyntheticBow(BowConfig(dim=c["dim"], informative_pool=c["informative_pool"], seed=3))
    s = b.sample_round(0, 1, N)
    return [np.asarray(x).reshape(N, -1) if x.ndim == 3 else np.asarray(x).reshape(N) for x in s]


def test_bow_mean_nnz_matches_synthetic_bow(bow, host_bow):
    idx, val, y = bow
    h_idx, h_val, h_y = host_bow
    nnz, h_nnz = (val > 0).sum(1).mean(), (h_val > 0).sum(1).mean()
    assert abs(nnz - 88.54) < 0.5 and abs(nnz - h_nnz) < 0.6
    # the balance of labels depends on the drawn truth, in both generators
    assert 0 < y.mean() < 1 and 0 < h_y.mean() < 1
    assert np.all(idx[val == 0] == 0)  # padding is inert
    assert idx.max() < 4000 and idx.min() >= 0


def test_bow_zipf_rank_frequencies_match_synthetic_bow(bow, host_bow):
    cdf = bow_zipf.zipf_cdf(4000, 1.05)
    p = np.diff(np.concatenate([[0.0], cdf]))
    for (idx, val) in ((bow[0], bow[1]), (host_bow[0], host_bow[1])):
        ids = idx[val > 0]
        freq = np.bincount(ids, minlength=4000) / ids.size
        for r in range(8):  # the head: rank r+1 is id r
            sd = np.sqrt(p[r] * (1 - p[r]) / ids.size)
            assert abs(freq[r] - p[r]) < 5 * sd, (r, freq[r], p[r])
        # the tail beyond rank 100 carries its share of the mass
        assert abs(freq[100:].sum() - p[100:].sum()) < 0.01


def test_bow_same_seed_same_inputs_and_blocks_do_not_depend_on_count():
    c = _bow_config(dim=1000)
    a = corpus.blocks(c, SEED, 3, (64,))
    b = corpus.blocks(c, SEED, 2, (64,))
    other = corpus.blocks(c, SEED + 1, 2, (64,))
    for k in ("idx", "val", "y"):
        np.testing.assert_array_equal(np.asarray(a[k])[:2], np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["idx"])[:2], np.asarray(other["idx"]))


def _ctr_config():
    c = copy.deepcopy(spec.config("ctr_criteo_hashed"))
    c["data"].update(dim=2**16, cardinalities=[min(x, 5000) for x in c["data"]["cardinalities"]])
    return c


def test_ctr_config_has_the_criteo_layout():
    c = spec.config("ctr_criteo_hashed")
    assert c["data"]["n_int"] == 13 and len(c["data"]["cardinalities"]) == 26
    assert c["p_max"] == 40 and c["data"]["dim"] == 2**26
    assert abs(sum(c["data"]["cardinalities"]) - 33.76e6) < 0.05e6


def test_ctr_rows_follow_the_criteo_layout():
    c = _ctr_config()
    idx, val, y = _flat(corpus.blocks(c, SEED, 2, (N // 2,)))
    n_int, n_cat = 13, 26
    assert idx.shape == (N, 40)
    # integer fields: one fixed id per field, value log1p(count) or 0 if missing
    assert np.all(idx[:, :n_int] == idx[0, :n_int])
    assert len(set(idx[0, :n_int].tolist())) == n_int
    # zero where missing (0.2) or where the count floors to 0 (exp(1 + 1.5 N) < 1)
    zero = 0.2 + 0.8 * 0.5 * (1 + math.erf(-1.0 / 1.5 / math.sqrt(2)))
    assert abs((val[:, :n_int] == 0).mean() - zero) < 0.02
    assert np.all(val[:, :n_int] >= 0)
    # categorical fields: value 1, hashed into [0, dim)
    assert np.all(val[:, n_int : n_int + n_cat] == 1.0)
    assert idx.min() >= 0 and idx.max() < 2**16
    # padding
    assert np.all(idx[:, n_int + n_cat :] == 0) and np.all(val[:, n_int + n_cat :] == 0)
    # clicks: about positive_rate of the rows
    assert abs(y.mean() - 0.256) < 0.02


def test_ctr_ranks_follow_their_power_law():
    import jax
    import jax.numpy as jnp

    card, s = 1000, 1.1
    u = jax.random.uniform(jax.random.key(0), (200000, 1))
    r = np.asarray(ctr_hashed.ranks(u, jnp.asarray([card]), s)).ravel()
    assert r.min() >= 0 and r.max() < card
    freq = np.bincount(r, minlength=card) / r.size
    law = np.diff(ctr_hashed.rank_cdf(np.arange(card + 1), card, s))
    for k in range(5):
        sd = np.sqrt(law[k] * (1 - law[k]) / r.size)
        assert abs(freq[k] - law[k]) < 5 * sd, (k, freq[k], law[k])
