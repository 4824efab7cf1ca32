"""Sparse-neighborhood fused training path == seed dense reference.

The production path (neighbor-table scatter + lax.scan epochs, optional
fused Pallas step) must reproduce the seed per-batch dense-M loop —
same losses, same factors — for every mode and for paper_literal
weighting. See DESIGN.md §5 for the equivalence argument.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dmf, graph
from repro.data import synthetic_poi
from repro.kernels import ops, ref


def _world(seed=0):
    ds = synthetic_poi.generate(synthetic_poi.POIDatasetConfig(
        n_users=80, n_items=50, n_ratings=600, n_cities=4, seed=seed))
    gcfg = graph.GraphConfig(n_neighbors=2, walk_length=3)
    W = graph.build_adjacency(ds.user_coords, ds.user_city, gcfg)
    return ds, gcfg, W


def test_neighbor_table_reconstructs_dense_m():
    ds, gcfg, W = _world()
    for cfg in [gcfg, graph.GraphConfig(n_neighbors=2, walk_length=3,
                                        paper_literal=True)]:
        M = graph.walk_propagation_matrix(W, cfg)
        nbr = graph.walk_neighbor_table(W, cfg)
        # S is the max realized 1 + |N^D(i)| (self always has M[i,i]=1)
        nnz = (M != 0).sum(axis=1)
        assert nbr.idx.shape == (ds.n_users, int(nnz.max()))
        Md = graph.dense_from_neighbor_table(nbr, ds.n_users)
        np.testing.assert_array_equal(Md, M)
        # padded slots are zero-weight self-indices -> scatter no-ops
        pad = np.asarray(nbr.wgt) == 0.0
        np.testing.assert_array_equal(
            np.asarray(nbr.idx)[pad],
            np.broadcast_to(np.arange(ds.n_users)[:, None], nbr.idx.shape)[pad],
        )


@pytest.mark.parametrize("mode", ["dmf", "gdmf", "ldmf"])
def test_scan_sparse_epoch_matches_dense_reference(mode):
    ds, gcfg, W = _world()
    M = graph.walk_propagation_matrix(W, gcfg)
    nbr = graph.walk_neighbor_table(W, gcfg)
    cfg = dmf.DMFConfig(n_users=ds.n_users, n_items=ds.n_items, dim=6,
                        mode=mode, batch_size=64, beta=0.1, gamma=0.01)
    rd = dmf.fit(cfg, ds.train, M, epochs=3, test=ds.test, dense_reference=True)
    rs = dmf.fit(cfg, ds.train, nbr, epochs=3, test=ds.test)
    np.testing.assert_allclose(rd.train_losses, rs.train_losses, atol=1e-4)
    np.testing.assert_allclose(rd.test_losses, rs.test_losses, atol=1e-4)
    np.testing.assert_allclose(np.asarray(rd.state.U), np.asarray(rs.state.U),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(rd.state.P), np.asarray(rs.state.P),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(rd.state.Q), np.asarray(rs.state.Q),
                               atol=1e-5)


def _scatter_case(repeats):
    """(P, recv, items, upd) for a small (I, J, K): walk-weighted messages,
    the last slot of every row a zero-weight self pad. With ``repeats``
    the same (receiver, item) pair is hit twice within row 0, and again
    across rows (rows 0 and 3 share item 2, rows 1 and 2 item 4); without,
    every live pair is distinct."""
    rng = np.random.default_rng(11)
    I, J, K = 9, 6, 3
    senders = np.asarray([0, 1, 2, 3], np.int32)
    if repeats:
        items = np.asarray([2, 4, 4, 2], np.int32)
        recv = np.asarray([[0, 5, 5, 0], [1, 5, 7, 1], [2, 5, 7, 2],
                           [3, 0, 5, 3]], np.int32)
    else:
        items = np.asarray([2, 4, 1, 5], np.int32)
        recv = np.asarray([[0, 5, 6, 0], [1, 5, 7, 1], [2, 8, 4, 2],
                           [3, 0, 8, 3]], np.int32)
    assert (recv[:, -1] == senders).all()
    wgt = rng.uniform(0.2, 1.0, recv.shape).astype(np.float32)
    wgt[:, -1] = 0.0
    gp = rng.normal(size=(len(senders), K)).astype(np.float32)
    upd = -0.1 * wgt[:, :, None] * gp[:, None, :]
    P = rng.normal(size=(I, J, K)).astype(np.float32)
    return (jnp.asarray(P), jnp.asarray(recv), jnp.asarray(items),
            jnp.asarray(upd))


@pytest.mark.parametrize("repeats", [True, False], ids=["repeats", "distinct"])
def test_p_scatter_add_matches_single_scatter(repeats):
    P, recv, items, upd = _scatter_case(repeats)
    got = np.asarray(jax.jit(dmf._p_scatter_add)(P, recv, items, upd))
    want = np.asarray(jax.jit(
        lambda P, n, v, u: P.at[n, v[:, None]].add(u))(P, recv, items, upd))
    pairs = {(int(n), int(v)) for row, v in zip(np.asarray(recv),
                                                 np.asarray(items))
             for n in row}
    touched = np.zeros(P.shape[:2], bool)
    touched[tuple(np.asarray(sorted(pairs)).T)] = True
    # rows and items nobody sends to, and the zero-weight pads, stay exact
    np.testing.assert_array_equal(got[~touched], np.asarray(P)[~touched])
    if repeats:
        np.testing.assert_allclose(got, want, rtol=1e-6)
    else:
        np.testing.assert_array_equal(got, want)


def test_scan_sparse_epoch_matches_dense_paper_literal():
    ds, _, W = _world()
    gcfg = graph.GraphConfig(n_neighbors=2, walk_length=2, paper_literal=True)
    M = graph.walk_propagation_matrix(W, gcfg)
    nbr = graph.walk_neighbor_table(W, gcfg)
    # tiny lr: the literal |N^d| amplification diverges fast otherwise
    cfg = dmf.DMFConfig(n_users=ds.n_users, n_items=ds.n_items, dim=4,
                        batch_size=64, lr=0.01)
    rd = dmf.fit(cfg, ds.train, M, epochs=2, dense_reference=True)
    rs = dmf.fit(cfg, ds.train, nbr, epochs=2)
    np.testing.assert_allclose(rd.train_losses, rs.train_losses, atol=1e-4)
    np.testing.assert_allclose(np.asarray(rd.state.P), np.asarray(rs.state.P),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["dmf", "gdmf", "ldmf"])
def test_pallas_fused_step_path_matches_jnp(mode):
    ds, gcfg, W = _world(seed=1)
    nbr = graph.walk_neighbor_table(W, gcfg)
    kw = dict(n_users=ds.n_users, n_items=ds.n_items, dim=6, mode=mode,
              batch_size=64)
    rj = dmf.fit(dmf.DMFConfig(**kw), ds.train, nbr, epochs=2, test=ds.test)
    rp = dmf.fit(dmf.DMFConfig(**kw, use_pallas=True), ds.train, nbr,
                 epochs=2, test=ds.test)
    np.testing.assert_allclose(rj.train_losses, rp.train_losses, atol=1e-4)
    np.testing.assert_allclose(np.asarray(rj.state.U), np.asarray(rp.state.U),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(rj.state.P), np.asarray(rp.state.P),
                               atol=1e-5)


def test_fused_step_kernel_matches_ref():
    rng = np.random.default_rng(3)
    B, K = 300, 10   # non-aligned on purpose: exercises batch + lane padding
    u, p, q = (jnp.asarray(rng.normal(size=(B, K)), jnp.float32) for _ in range(3))
    r = jnp.asarray(rng.integers(0, 2, B), jnp.float32)
    c = jnp.asarray(rng.uniform(0.2, 1.0, B), jnp.float32)
    got = ops.dmf_fused_step(u, p, q, r, c, theta=0.1, alpha=0.3, beta=0.2,
                             gamma=0.1)
    want = ref.dmf_fused_step_ref(u, p, q, r, c, 0.1, 0.3, 0.2, 0.1)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_streaming_evaluate_matches_dense_evaluate():
    ds, gcfg, W = _world()
    nbr = graph.walk_neighbor_table(W, gcfg)
    cfg = dmf.DMFConfig(n_users=ds.n_users, n_items=ds.n_items, dim=6,
                        beta=0.1, gamma=0.01, batch_size=64)
    res = dmf.fit(cfg, ds.train, nbr, epochs=10)
    ev_s = dmf.evaluate(res.state, ds.train, ds.test, ds.n_users, ds.n_items)
    ev_d = dmf.evaluate_dense(res.state, ds.train, ds.test, ds.n_users, ds.n_items)
    for k in ev_d:
        np.testing.assert_allclose(ev_s[k], ev_d[k], atol=1e-9, err_msg=k)


def test_recommend_topk_peruser_matches_ref():
    rng = np.random.default_rng(5)
    I, J, K, k = 70, 90, 7, 10
    U = jnp.asarray(rng.normal(size=(I, K)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(I, J, K)), jnp.float32)
    mask = jnp.asarray(rng.random((I, J)) < 0.2)
    vals, idx = ops.recommend_topk_peruser(U, V, mask, k)
    v_ref, i_ref = ref.topk_scores_peruser_ref(U, V, mask, k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(v_ref),
                               rtol=1e-5, atol=1e-6)
    # continuous random scores: ties have measure zero -> indices agree
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i_ref))
