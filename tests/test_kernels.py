"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("B", [64, 256, 300, 1024])
@pytest.mark.parametrize("K", [5, 10, 15, 128])
def test_dmf_grads_shapes(B, K):
    rng = np.random.default_rng(B * K)
    u, p, q = (jnp.asarray(rng.normal(size=(B, K)), jnp.float32) for _ in range(3))
    r = jnp.asarray(rng.random(B), jnp.float32)
    c = jnp.asarray(rng.random(B), jnp.float32)
    got = ops.dmf_grads(u, p, q, r, c, alpha=0.1, beta=0.01, gamma=0.02)
    want = ref.dmf_grads_ref(u, p, q, r, c, 0.1, 0.01, 0.02)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 400), st.integers(1, 40), st.integers(0, 99))
def test_dmf_grads_property(B, K, seed):
    rng = np.random.default_rng(seed)
    u, p, q = (jnp.asarray(rng.normal(size=(B, K)), jnp.float32) for _ in range(3))
    r = jnp.asarray(rng.random(B), jnp.float32)
    c = jnp.asarray(rng.random(B), jnp.float32)
    got = ops.dmf_grads(u, p, q, r, c, alpha=0.3, beta=0.2, gamma=0.1)
    want = ref.dmf_grads_ref(u, p, q, r, c, 0.3, 0.2, 0.1)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("I,F", [(128, 128), (200, 333), (512, 64), (77, 1000)])
def test_gossip_mix_shapes(I, F):
    rng = np.random.default_rng(I + F)
    M = jnp.asarray(rng.normal(size=(I, I)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(I, F)), jnp.float32)
    got = ops.gossip_mix_op(M, X)
    want = ref.gossip_mix_ref(M, X)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_gossip_mix_dtype_bf16_inputs_upcast():
    rng = np.random.default_rng(0)
    M = jnp.asarray(rng.normal(size=(64, 64)), jnp.bfloat16)
    X = jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16)
    got = ops.gossip_mix_op(M, X)
    want = ref.gossip_mix_ref(M.astype(jnp.float32), X.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("I,J,K,k", [
    (128, 256, 8, 5), (150, 500, 12, 10), (64, 1000, 15, 16), (256, 256, 5, 1),
])
def test_topk_scores_shapes(I, J, K, k):
    rng = np.random.default_rng(I + J + k)
    U = jnp.asarray(rng.normal(size=(I, K)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(J, K)), jnp.float32)
    mask = jnp.asarray(rng.random((I, J)) < 0.1)
    v1, i1 = ops.recommend_topk(U, V, mask, k)
    v2, i2 = ref.topk_scores_ref(U, V, mask, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5, atol=1e-5)
    assert (np.asarray(i1) == np.asarray(i2)).mean() > 0.999  # ties may differ


@settings(max_examples=8, deadline=None)
@given(st.integers(4, 100), st.integers(8, 300), st.integers(1, 8), st.integers(0, 99))
def test_topk_property_values_sorted_and_unmasked(I, J, k, seed):
    rng = np.random.default_rng(seed)
    U = jnp.asarray(rng.normal(size=(I, 6)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(J, 6)), jnp.float32)
    mask = jnp.asarray(rng.random((I, J)) < 0.2)
    k = min(k, J)
    vals, idx = ops.recommend_topk(U, V, mask, k)
    vals, idx = np.asarray(vals), np.asarray(idx)
    assert (np.diff(vals, axis=1) <= 1e-6).all(), "values sorted desc"
    m = np.asarray(mask)
    for i in range(I):
        valid = idx[i][idx[i] >= 0]
        assert (valid < J).all()
        assert not m[i, valid].any(), "masked (train) item recommended"


@pytest.mark.parametrize("path", ["peruser", "window"])
def test_topk_ties_keep_lowest_ids_across_tiles(path):
    """Tie contract: equal scores rank by ascending item id. A better item
    in a later tile takes a slot held by a tied item; the displaced item
    moves down past the higher ids tied with it instead of dropping out."""
    R, J, K, k = 8, 384, 4, 6
    u = jnp.ones((R, K), jnp.float32)
    v = np.zeros((R, J, K), np.float32)     # every score ties at 0 ...
    v[:, 0] = 1.0                            # ... but item 0 (tile 0)
    v[:, 300] = 0.5                          # ... and item 300 (tile 2)
    v = jnp.asarray(v)
    if path == "peruser":
        _, idx = ops.recommend_topk_peruser(u, v, jnp.zeros((R, J), bool), k)
    else:
        cand = jnp.tile(jnp.arange(J, dtype=jnp.int32), (R, 1))
        _, idx = ops.serve_topk_window(u, v, cand,
                                       jnp.zeros((R, J), jnp.int8), k)
    np.testing.assert_array_equal(
        np.asarray(idx), np.tile([0, 300, 1, 2, 3, 4], (R, 1)))
