"""Million-user tiled serving: window kernel == slab kernel (bitwise) ==
dense oracle (ids exact, scores within ref.MAX_ULP; tie-heavy zero-init
inputs included), quantized-V error bounds,
cold-city / empty-input candidate-index regressions, chunked eligibility,
hierarchical geohash-cell index invariants, TiledServingEngine parity with
the classic ServingEngine, streaming evaluate exactness, and a slow
1M-user peak-memory smoke."""
import dataclasses

import numpy as np
import pytest

from repro.core import dmf, graph, metrics
from repro.data import synthetic_poi
from repro.kernels import ops, ref
from repro.serving import (ServingConfig, ServingEngine, SyntheticFactors,
                           TiledFactorStore, TiledServingEngine,
                           build_candidate_index, build_hierarchical_index,
                           index_from_dataset, synthetic_world)

pytestmark = pytest.mark.serving


def _world(seed=0, epochs=4):
    ds = synthetic_poi.generate(synthetic_poi.POIDatasetConfig(
        n_users=80, n_items=50, n_ratings=600, n_cities=4, seed=seed))
    gcfg = graph.GraphConfig(n_neighbors=2, walk_length=3)
    W = graph.build_adjacency(ds.user_coords, ds.user_city, gcfg)
    nbr = graph.walk_neighbor_table(W, gcfg)
    cfg = dmf.DMFConfig(n_users=ds.n_users, n_items=ds.n_items, dim=6,
                        beta=0.1, gamma=0.01, batch_size=64)
    res = dmf.fit(cfg, ds.train, nbr, epochs=epochs)
    return ds, nbr, cfg, res.state


def _random_windows(rng, R, J, Cw, K, zero_factors=False):
    """Random per-request candidate windows + matching dense inputs so the
    window kernel can be cross-checked against the whole-slab kernel and
    the dense oracle on identical problems."""
    U = rng.standard_normal((R, K)).astype(np.float32)
    V = rng.standard_normal((R, J, K)).astype(np.float32)
    if zero_factors:
        # tie-heavy regime: zero factors make every candidate score 0.0 —
        # the tie-break contract (lowest candidate id wins) is all that
        # orders the slate, exactly the zero-init serving cold-start case.
        U[:] = 0.0
        V[:] = 0.0
    seen = (rng.random((R, J)) < 0.2)
    cand = np.full((R, Cw), -1, np.int32)
    for r in range(R):
        n = rng.integers(1, Cw + 1)
        cand[r, :n] = np.sort(rng.choice(J, size=n, replace=False))
    safe = np.maximum(cand, 0)
    Vw = V[np.arange(R)[:, None], safe]                       # (R, Cw, K)
    seen_w = np.where(cand >= 0, seen[np.arange(R)[:, None], safe], False)
    return U, V, seen, cand, Vw, seen_w.astype(np.int8)


# ------------------------------------------------------- tiled kernel family
@pytest.mark.parametrize("zero_factors", [False, True],
                         ids=["random", "tie-heavy-zero-init"])
def test_window_kernel_matches_slab_and_oracle(zero_factors):
    rng = np.random.default_rng(0)
    R, J, Cw, K, k = 5, 40, 17, 6, 8
    U, V, seen, cand, Vw, seen_w = _random_windows(
        rng, R, J, Cw, K, zero_factors)
    wv, wi = ops.serve_topk_window(U, Vw, cand, seen_w, k)
    sv, si = ops.serve_topk(U, V, cand, seen, k)
    rv, ri = ref.serve_topk_window_ref(U, Vw, cand, seen_w, k)
    dv, di = ref.serve_topk_ref(U, V, cand, seen, k)
    # window kernel == slab kernel bitwise (same kernel body); both oracles
    # under the serving contract
    np.testing.assert_array_equal(np.asarray(wi), np.asarray(si))
    np.testing.assert_array_equal(np.asarray(wv), np.asarray(sv))
    for v2, i2 in [(rv, ri), (dv, di)]:
        ref.assert_topk_matches(wv, wi, v2, i2, U, Vw)
    if zero_factors:
        # the slate is ordered purely by the tie contract: ascending
        # candidate ids among unseen candidates
        for r in range(R):
            unseen = cand[r][(cand[r] >= 0) & (seen_w[r] == 0)]
            want = np.sort(unseen)[:k]
            got = np.asarray(wi)[r][np.asarray(wi)[r] >= 0]
            np.testing.assert_array_equal(got, want)


def test_window_kernel_multiple_tiles_and_padding():
    # Cw spanning several 128-lane tiles with a ragged tail exercises the
    # inner-grid streaming and the -1 padding path together.
    rng = np.random.default_rng(1)
    R, J, Cw, K, k = 9, 700, 300, 8, 10
    U, V, seen, cand, Vw, seen_w = _random_windows(rng, R, J, Cw, K)
    wv, wi = ops.serve_topk_window(U, Vw, cand, seen_w, k)
    rv, ri = ref.serve_topk_window_ref(U, Vw, cand, seen_w, k)
    ref.assert_topk_matches(wv, wi, rv, ri, U, Vw)


def test_quant_kernel_bitwise_equals_dequantized_window():
    """The int8 kernel's in-kernel dequant (codes·scale, f32) must equal
    running the fp32 window kernel on host-dequantized values — bitwise,
    since both perform the identical f32 multiply before the contraction."""
    rng = np.random.default_rng(2)
    R, J, Cw, K, k = 6, 60, 20, 5, 7
    U, V, seen, cand, Vw, seen_w = _random_windows(rng, R, J, Cw, K)
    scale = np.maximum(np.abs(Vw).max(axis=(1, 2)) / 127.0, 1e-12)
    scale = scale.astype(np.float32)
    codes = np.clip(np.rint(Vw / scale[:, None, None]), -127, 127
                    ).astype(np.int8)
    qv, qi = ops.serve_topk_window_quant(U, codes, scale, cand, seen_w, k)
    deq = codes.astype(np.float32) * scale[:, None, None]
    fv, fi = ops.serve_topk_window(U, deq, cand, seen_w, k)
    np.testing.assert_array_equal(np.asarray(qi), np.asarray(fi))
    np.testing.assert_array_equal(np.asarray(qv), np.asarray(fv))


def test_quant_scores_within_analytic_bound_and_exact_on_gaps():
    rng = np.random.default_rng(3)
    R, Cw, K, k = 8, 24, 6, 5
    U = rng.standard_normal((R, K)).astype(np.float32)
    # gap-separated construction: candidate c of request r scores ~ 3·c,
    # far above any quantization error, so int8 must return the exact
    # fp32 top-k slate (overlap 1.0), not merely a close one.
    Vw = np.zeros((R, Cw, K), np.float32)
    for r in range(R):
        u = U[r]
        Vw[r] = np.outer(3.0 * np.arange(Cw), u / (u @ u))
    cand = np.tile(np.arange(Cw, dtype=np.int32), (R, 1))
    seen_w = np.zeros((R, Cw), np.int8)
    scale = np.maximum(np.abs(Vw).max(axis=(1, 2)) / 127.0, 1e-12
                       ).astype(np.float32)
    codes = np.clip(np.rint(Vw / scale[:, None, None]), -127, 127
                    ).astype(np.int8)
    qv, qi = ops.serve_topk_window_quant(U, codes, scale, cand, seen_w, k)
    fv, fi = ops.serve_topk_window(U, Vw, cand, seen_w, k)
    np.testing.assert_array_equal(np.asarray(qi), np.asarray(fi))
    bound = np.abs(U).sum(axis=1) * scale * 0.5        # ||u||₁ · scale/2
    delta = np.abs(np.asarray(qv) - np.asarray(fv))
    assert (delta <= bound[:, None] + 1e-6).all(), (delta.max(), bound)


# ----------------------------------------------- cold-city index regressions
def test_build_candidate_index_city_with_users_but_no_items():
    """Regression: a city appearing only in user_city used to crash the
    builder (C was derived from item_city alone, so user buckets indexed
    out of range). Such users get an empty bucket, not a crash."""
    item_city = np.array([0, 0, 1], np.int64)
    user_city = np.array([0, 1, 2, 2], np.int64)   # city 2 has no POIs
    idx = build_candidate_index(item_city, user_city)
    assert idx.n_buckets == 3
    assert idx.bucket_size[2] == 0
    assert (idx.bucket_items[2] == -1).all()
    np.testing.assert_array_equal(idx.user_bucket, user_city)


def test_build_candidate_index_empty_arrays():
    idx = build_candidate_index(np.empty(0, np.int64), np.empty(0, np.int64))
    assert idx.n_buckets == 1 and (idx.bucket_items == -1).all()
    idx2 = build_candidate_index(np.array([0, 1]), np.empty(0, np.int64))
    assert idx2.n_buckets == 2 and len(idx2.user_bucket) == 0


def test_engine_cold_city_fallback_round_trip():
    """End-to-end: users whose city has zero POIs are served the flagged
    popularity slate by both engines (classic and tiled), identically."""
    ds, nbr, cfg, state = _world()
    user_city = ds.user_city.copy()
    user_city[:5] = ds.item_city.max() + 1   # rehome 5 users to a POI-less city
    idx = build_candidate_index(ds.item_city, user_city)
    seen = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    eng = ServingEngine(state, idx, ServingConfig(microbatch=32),
                        train=ds.train)
    v1, i1, f1 = eng.recommend(np.arange(ds.n_users), return_flags=True)
    assert f1[:5].all()
    np.testing.assert_array_equal(np.asarray(i1)[:5],
                                  np.tile(eng._pop_items, (5, 1)))
    store = TiledFactorStore.from_state(state, idx, seen)
    teng = TiledServingEngine(store, ServingConfig(microbatch=32))
    v2, i2, f2 = teng.recommend(np.arange(ds.n_users), return_flags=True)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(np.asarray(i1), i2)
    np.testing.assert_array_equal(np.asarray(v1), v2)


def test_eligible_mask_chunked_matches_whole():
    ds, *_ = _world(epochs=0)
    idx = index_from_dataset(ds)
    users = np.arange(ds.n_users)
    whole = idx.eligible_mask(users)
    parts = list(idx.eligible_mask_chunks(users, rows_per_chunk=7))
    assert [s for s, _ in parts] == list(range(0, ds.n_users, 7))
    np.testing.assert_array_equal(np.concatenate([m for _, m in parts]), whole)
    np.testing.assert_array_equal(idx.eligible_mask(users, rows_per_chunk=7),
                                  whole)


# ------------------------------------------------------- hierarchical index
def test_hierarchical_index_invariants():
    rng = np.random.default_rng(4)
    uc, ic, ucoord, icoord = synthetic_world(3000, 800, 6, seed=5)
    hier = build_hierarchical_index(ic, uc, icoord, ucoord, cell_cap=64)
    flat = hier.flat
    # every item lands in exactly one cell, of its own city and ≤ cell_cap
    assert hier.cell_of_item.min() >= 0
    for c in range(hier.n_cells):
        members = np.flatnonzero(hier.cell_of_item == c)
        assert len(members) <= 64
        if len(members):
            assert (ic[members] == hier.cell_city[c]).all()
        # the flat index bucket holds exactly the cell's items, ascending
        row = flat.bucket_items[c]
        np.testing.assert_array_equal(row[row >= 0], members)
    # users are assigned to cells of their own city
    assert (hier.cell_city[hier.cell_of_user] == uc).all()
    np.testing.assert_array_equal(flat.user_bucket, hier.cell_of_user)
    # subdivision actually engaged (cities are bigger than cell_cap)
    assert hier.n_cells > 6 and hier.max_depth >= 1
    st = hier.stats()
    assert st["n_cells"] == hier.n_cells and st["cap"] == flat.cap


def test_hierarchical_cells_reduce_cap():
    uc, ic, ucoord, icoord = synthetic_world(2000, 4000, 4, seed=6)
    flat = build_candidate_index(ic, uc)
    hier = build_hierarchical_index(ic, uc, icoord, ucoord, cell_cap=128)
    assert hier.flat.cap < flat.cap    # the point of the hierarchy


# --------------------------------------------------- tiled store and engine
def test_tiled_store_matches_serving_engine_bitwise():
    ds, nbr, cfg, state = _world()
    idx = index_from_dataset(ds)
    seen = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    eng = ServingEngine(state, idx, ServingConfig(microbatch=32),
                        train=ds.train)
    store = TiledFactorStore.from_state(state, idx, seen)
    teng = TiledServingEngine(store, ServingConfig(microbatch=32))
    uids = np.concatenate([np.arange(ds.n_users), [-1, ds.n_users + 7]])
    v1, i1, f1 = eng.recommend(uids, return_flags=True)
    v2, i2, f2 = teng.recommend(uids, return_flags=True)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(np.asarray(i1), i2)
    np.testing.assert_array_equal(np.asarray(v1), v2)


def test_tiled_store_quantized_modes_bounded():
    ds, nbr, cfg, state = _world()
    idx = index_from_dataset(ds)
    seen = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    store = TiledFactorStore.from_state(state, idx, seen)
    store.quantize_int8()
    store.quantize_bf16()
    nb = store.nbytes()
    assert nb["slab_int8"] < nb["slab_fp32"] / 3
    assert nb["slab_bf16"] == nb["slab_fp32"] // 2
    users = np.arange(ds.n_users)
    fp = TiledServingEngine(store, ServingConfig(microbatch=32))
    vf, iff, fl = fp.recommend(users, return_flags=True)
    cand = idx.bucket_items[idx.user_bucket[users]]
    for mode, bound in [("int8", store.int8_score_bound(users)),
                        ("bf16", store.bf16_score_bound(users))]:
        qe = TiledServingEngine(store, ServingConfig(microbatch=32), mode=mode)
        vq, iq, flq = qe.recommend(users, return_flags=True)
        np.testing.assert_array_equal(fl, flq)
        for r in np.flatnonzero(~fl):
            sc = store.slab[r] @ store.U[r]       # fp32 scores of the window
            for slot in range(qe.cfg.k):
                j = iq[r, slot]
                if j < 0:
                    continue
                pos = np.flatnonzero(cand[r] == j)
                assert len(pos) == 1
                assert abs(float(vq[r, slot]) - float(sc[pos[0]])) \
                    <= bound[r] + 1e-6, (mode, r, slot)


def test_tiled_store_shard_rows_parity():
    ds, nbr, cfg, state = _world()
    idx = index_from_dataset(ds)
    seen = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    store = TiledFactorStore.from_state(state, idx, seen)
    full = TiledServingEngine(store, ServingConfig(microbatch=16))
    vf, iff = full.recommend(np.arange(ds.n_users))
    for s, sub in store.shard_rows(3):
        assert sub.slab.base is store.slab        # views, not copies
        se = TiledServingEngine(sub, ServingConfig(microbatch=16))
        vs, is_ = se.recommend(np.arange(sub.n_users))
        np.testing.assert_array_equal(vs, vf[s: s + sub.n_users])
        np.testing.assert_array_equal(is_, iff[s: s + sub.n_users])


def test_synthetic_store_windows_match_dense_generator():
    uc, ic, ucoord, icoord = synthetic_world(1500, 400, 5, seed=7)
    hier = build_hierarchical_index(ic, uc, icoord, ucoord, cell_cap=64)
    sf = SyntheticFactors.create(1500, 400, 8, seed=8)
    store = TiledFactorStore.synthetic(sf, hier.flat, seen_per_user=3, seed=9)
    samp = np.arange(0, 1500, 97)
    dense = sf.dense_rows(samp)               # (n, J, K) oracle item views
    cand = hier.flat.bucket_items[hier.flat.user_bucket[samp]]
    for r, u in enumerate(samp):
        m = cand[r] >= 0
        np.testing.assert_array_equal(dense[r][cand[r][m]], store.slab[u][m])
    assert int(store.item_counts.sum()) == int(store.seen.sum())


# ------------------------------------------------------- streaming evaluate
def test_evaluate_chunked_exactly_matches_unchunked():
    ds, nbr, cfg, state = _world()
    base = dmf.evaluate(state, ds.train, ds.test, ds.n_users, ds.n_items)
    for chunk in (7, 32, 1000):
        got = dmf.evaluate(state, ds.train, ds.test, ds.n_users, ds.n_items,
                           chunk_users=chunk)
        assert got == base, (chunk, got, base)


@pytest.mark.sharded
def test_evaluate_sharded_chunked_exactly_matches():
    ds, nbr, cfg, state = _world()
    base = dmf.evaluate(state, ds.train, ds.test, ds.n_users, ds.n_items)
    sh = dmf.evaluate(state, ds.train, ds.test, ds.n_users, ds.n_items,
                      n_shards=4)
    assert sh == base
    for chunk in (5, 16):
        got = dmf.evaluate(state, ds.train, ds.test, ds.n_users, ds.n_items,
                           n_shards=4, chunk_users=chunk)
        assert got == base, (chunk, got, base)


# --------------------------------------------------------- million-user smoke
@pytest.mark.slow
def test_million_user_store_bounded_memory():
    """1M users × 100k POIs, K=4: build the synthetic world + hierarchical
    index + tiled store and serve a batch, asserting peak RSS stays far
    below what any dense per-user item view would need (the fp32 slab at
    cell_cap=128 is ~2 GB; a single dense (I, J) score matrix alone would
    be 400 GB). Runs in a subprocess so the RSS measurement is isolated."""
    from conftest import run_in_subprocess_with_devices
    out = run_in_subprocess_with_devices("""
import resource
import numpy as np
from repro.serving import (ServingConfig, SyntheticFactors, TiledFactorStore,
                           TiledServingEngine, build_hierarchical_index,
                           synthetic_world)

I, J, K = 1_000_000, 100_000, 4
uc, ic, ucoord, icoord = synthetic_world(I, J, n_cities=1024, seed=0)
hier = build_hierarchical_index(ic, uc, icoord, ucoord, cell_cap=128)
sf = SyntheticFactors.create(I, J, K, seed=1)
store = TiledFactorStore.synthetic(sf, hier.flat, seen_per_user=2, seed=2)
eng = TiledServingEngine(store, ServingConfig(microbatch=128, k=10))
rng = np.random.default_rng(3)
vals, idx, flags = eng.recommend(rng.integers(0, I, 512), return_flags=True)
assert vals.shape == (512, 10) and (idx[~flags] >= 0).any()
peak_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
print('cap', store.cap, 'cells', hier.n_cells, 'peak_gb', round(peak_gb, 2))
assert peak_gb < 12.0, peak_gb
""", n_devices=1, timeout=1200)
    assert "peak_gb" in out
