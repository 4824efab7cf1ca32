"""Serving subsystem: candidate index, fused serve kernel, engine == dense
oracle, microbatcher, and online refresh (locality + tracking)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dmf, graph, metrics
from repro.data import synthetic_poi
from repro.kernels import ops, ref
from repro.serving import (OnlineConfig, ServingConfig, ServingEngine,
                           build_candidate_index, index_from_dataset,
                           online_refresh)

pytestmark = pytest.mark.serving


def _world(seed=0, epochs=6):
    ds = synthetic_poi.generate(synthetic_poi.POIDatasetConfig(
        n_users=80, n_items=50, n_ratings=600, n_cities=4, seed=seed))
    gcfg = graph.GraphConfig(n_neighbors=2, walk_length=3)
    W = graph.build_adjacency(ds.user_coords, ds.user_city, gcfg)
    nbr = graph.walk_neighbor_table(W, gcfg)
    cfg = dmf.DMFConfig(n_users=ds.n_users, n_items=ds.n_items, dim=6,
                        beta=0.1, gamma=0.01, batch_size=64)
    res = dmf.fit(cfg, ds.train, nbr, epochs=epochs)
    return ds, nbr, cfg, res.state


# --------------------------------------------------------------- candidates
def test_candidate_index_structure():
    ds, *_ = _world(epochs=0)
    idx = index_from_dataset(ds)
    assert idx.cap % 128 == 0
    assert idx.bucket_items.shape == (idx.n_buckets, idx.cap)
    for c in range(idx.n_buckets):
        row = idx.bucket_items[c]
        items = row[row >= 0]
        # exactly the city's items, ascending, padding all -1 at the tail
        np.testing.assert_array_equal(items, np.flatnonzero(ds.item_city == c))
        assert (row[len(items):] == -1).all()
    assert idx.n_truncated_buckets == 0
    assert idx.user_fits().all()
    # eligibility oracle rows match the buckets
    elig = idx.eligible_mask(np.arange(ds.n_users))
    for u in range(ds.n_users):
        np.testing.assert_array_equal(
            np.flatnonzero(elig[u]), np.flatnonzero(ds.item_city == ds.user_city[u]))


def test_candidate_index_truncation_priority():
    item_city = np.zeros(300, np.int64)      # one city of 300 > cap=128
    user_city = np.zeros(4, np.int64)
    pop = np.arange(300)                     # priority = item id
    idx = build_candidate_index(item_city, user_city, cap=128,
                                item_priority=pop)
    assert idx.cap == 128
    assert idx.n_truncated_buckets == 1
    assert not idx.user_fits().any()
    kept = idx.bucket_items[0]
    # highest-priority 128 items survive, re-sorted ascending (contractual)
    np.testing.assert_array_equal(kept, np.arange(300 - 128, 300))


# ------------------------------------------------------------- serve kernel
def _random_candidates(rng, R, J, Cw):
    cand = np.full((R, Cw), -1, np.int32)
    for r in range(R):
        n = rng.integers(0, min(J, Cw) + 1)
        cand[r, :n] = np.sort(rng.choice(J, size=n, replace=False))
    return cand


@pytest.mark.parametrize("R,J,K,Cw,k", [
    (13, 90, 10, 37, 7),     # nothing aligned: exercises all pads
    (8, 128, 8, 128, 5),     # fully aligned
    (3, 300, 6, 260, 10),    # J and Cw span multiple item tiles
])
def test_serve_topk_matches_oracle_exactly(R, J, K, Cw, k):
    rng = np.random.default_rng(R + J + k)
    U = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(R, J, K)), jnp.float32)
    seen = jnp.asarray(rng.random((R, J)) < 0.3)
    cand = jnp.asarray(_random_candidates(rng, R, J, Cw))
    vals, idx = ops.serve_topk(U, V, cand, seen, k)
    v_ref, i_ref = ref.serve_topk_ref(U, V, cand, seen, k)
    ref.assert_topk_matches(vals, idx, v_ref, i_ref, U, V)


def test_serve_topk_exact_ties_break_by_lowest_id():
    # zero item factors -> every candidate scores exactly 0.0; the kernel
    # must resolve ties like lax.top_k: lowest item id first
    rng = np.random.default_rng(0)
    R, J, K, k = 5, 60, 4, 6
    U = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    V = jnp.zeros((R, J, K), jnp.float32)
    seen = jnp.zeros((R, J), bool)
    cand = jnp.asarray(_random_candidates(rng, R, J, 40))
    vals, idx = ops.serve_topk(U, V, cand, seen, k)
    v_ref, i_ref = ref.serve_topk_ref(U, V, cand, seen, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(v_ref))


def test_serve_topk_k_exceeds_bucket_size():
    rng = np.random.default_rng(1)
    R, J, K, k = 6, 50, 5, 10
    U = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(R, J, K)), jnp.float32)
    seen = jnp.zeros((R, J), bool)
    cand = np.full((R, 16), -1, np.int32)
    for r in range(R):                       # buckets of size 0..5 < k
        cand[r, : r] = np.arange(r) * 7
    vals, idx = ops.serve_topk(U, V, jnp.asarray(cand), seen, k)
    v_ref, i_ref = ref.serve_topk_ref(U, V, jnp.asarray(cand), seen, k)
    ref.assert_topk_matches(vals, idx, v_ref, i_ref, U, V)
    for r in range(R):                       # exactly bucket-size slots fill
        assert (np.asarray(idx)[r] >= 0).sum() == r


def test_serve_topk_all_seen_users():
    rng = np.random.default_rng(2)
    R, J, K, k = 4, 40, 6, 5
    U = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(R, J, K)), jnp.float32)
    cand = jnp.asarray(_random_candidates(rng, R, J, 24))
    seen = jnp.ones((R, J), bool)
    vals, idx = ops.serve_topk(U, V, cand, seen, k)
    assert (np.asarray(idx) == -1).all()
    assert (np.asarray(vals) <= ref.NEG_INF).all()
    v_ref, i_ref = ref.serve_topk_ref(U, V, cand, seen, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i_ref))


# --------------------------------------------- peruser kernel edge coverage
def _peruser_oracle(U, V, mask, k):
    vals, idx = ref.topk_scores_peruser_ref(U, V, mask, k)
    return ref.masked_topk_finalize(jnp.where(jnp.isneginf(vals),
                                              ref.NEG_INF, vals), idx)


def test_recommend_topk_peruser_j_not_tile_divisible():
    rng = np.random.default_rng(3)
    I, J, K, k = 20, 130, 7, 5         # J % 128 != 0 -> wrapper pads items
    U = jnp.asarray(rng.normal(size=(I, K)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(I, J, K)), jnp.float32)
    mask = jnp.asarray(rng.random((I, J)) < 0.2)
    vals, idx = ops.recommend_topk_peruser(U, V, mask, k)
    v_ref, i_ref = _peruser_oracle(U, V, mask, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(v_ref),
                               rtol=1e-6, atol=1e-6)
    assert (np.asarray(idx) < J).all(), "padded item column recommended"


def test_recommend_topk_peruser_k_exceeds_unseen():
    rng = np.random.default_rng(4)
    I, J, K, k = 8, 30, 5, 16
    U = jnp.asarray(rng.normal(size=(I, K)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(I, J, K)), jnp.float32)
    mask = np.ones((I, J), bool)
    mask[:, :4] = False                   # only 4 unseen items, k=16
    vals, idx = ops.recommend_topk_peruser(U, V, jnp.asarray(mask), k)
    v_ref, i_ref = _peruser_oracle(U, V, jnp.asarray(mask), k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i_ref))
    assert ((np.asarray(idx)[:, 4:]) == -1).all()


def test_recommend_topk_peruser_all_seen():
    rng = np.random.default_rng(5)
    I, J, K, k = 6, 64, 4, 5
    U = jnp.asarray(rng.normal(size=(I, K)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(I, J, K)), jnp.float32)
    mask = jnp.ones((I, J), bool)
    vals, idx = ops.recommend_topk_peruser(U, V, mask, k)
    assert (np.asarray(idx) == -1).all()
    assert (np.asarray(vals) <= ref.NEG_INF).all()


# ------------------------------------------------------------------- engine
def test_engine_pruned_matches_serve_oracle_exactly():
    ds, nbr, cfg, state = _world()
    index = index_from_dataset(ds)
    # fallback=False: this is the raw factor-scoring kernel oracle — cold
    # users must go through the same path (fallback exactness is covered by
    # the dedicated fallback suite below)
    eng = ServingEngine(state, index,
                        ServingConfig(microbatch=16, k=5, fallback=False),
                        train=ds.train)
    users = np.random.default_rng(7).integers(0, ds.n_users, 53)
    vals, idx = eng.recommend(users)
    v_ref, i_ref = ref.serve_topk_ref(
        jnp.asarray(state.U[users]),
        jnp.asarray((state.P + state.Q)[users]),
        jnp.asarray(index.bucket_items[index.user_bucket[users]]),
        jnp.asarray(np.asarray(eng.seen)[users]), 5)
    ref.assert_topk_matches(vals, idx, v_ref, i_ref, state.U[users],
                            (state.P + state.Q)[users])
    assert eng.stats.n_requests == 53
    assert eng.stats.n_dispatches == 4       # ceil(53 / 16) fixed-shape batches


def test_engine_equals_full_dense_oracle_where_topk_in_bucket():
    """Acceptance: engine top-k == dense scores() + mask + top_k (item
    ids exactly, scores within ref.MAX_ULP), for users whose dense top-k
    fits the bucket."""
    ds, nbr, cfg, state = _world(epochs=10)
    index = index_from_dataset(ds)
    eng = ServingEngine(state, index,
                        ServingConfig(microbatch=32, k=5, fallback=False),
                        train=ds.train)
    users = np.arange(ds.n_users)
    vals, idx = eng.recommend(users)
    # dense full-J oracle, same score contraction as scores(): u · (p + q)
    V = state.P + state.Q
    full_cand = jnp.broadcast_to(jnp.arange(ds.n_items, dtype=jnp.int32),
                                 (ds.n_users, ds.n_items))
    dv, di = ref.serve_topk_ref(
        jnp.asarray(state.U), jnp.asarray(V), full_cand,
        jnp.asarray(np.asarray(eng.seen)), 5)
    dv, di = np.asarray(dv), np.asarray(di)
    in_bucket = np.array([
        np.isin(di[u][di[u] >= 0],
                index.bucket_items[index.user_bucket[u]]).all()
        for u in range(ds.n_users)])
    assert in_bucket.any(), "no user's dense top-k fits their bucket"
    ref.assert_topk_matches(vals[in_bucket], idx[in_bucket],
                            dv[in_bucket], di[in_bucket],
                            state.U[in_bucket], V[in_bucket])


def test_engine_dense_path_matches_peruser_kernel():
    ds, nbr, cfg, state = _world()
    index = index_from_dataset(ds)
    eng = ServingEngine(state, index,
                        ServingConfig(microbatch=16, k=5, prune=False),
                        train=ds.train)
    users = np.random.default_rng(8).integers(0, ds.n_users, 20)
    _, idx = eng.recommend(users)
    _, i_ref = ops.recommend_topk_peruser(
        jnp.asarray(state.U[users]),
        jnp.asarray((state.P + state.Q)[users]),
        jnp.asarray(np.asarray(eng.seen)[users]), 5)
    np.testing.assert_array_equal(idx, np.asarray(i_ref))


def test_engine_never_recommends_seen_or_out_of_city():
    """Serving contract under the default config: factor-scored users never
    get a seen or out-of-city item; only cold users (no train interactions,
    so no meaningful factors AND nothing 'seen') may receive the flagged
    popularity slate, which is city-agnostic by design."""
    ds, nbr, cfg, state = _world()
    index = index_from_dataset(ds)
    eng = ServingEngine(state, index, ServingConfig(microbatch=16, k=10),
                        train=ds.train)
    train_mask = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    users = np.arange(ds.n_users)
    _, idx, flags = eng.recommend(users, return_flags=True)
    cold = ~train_mask.any(axis=1)
    np.testing.assert_array_equal(flags, cold)     # only cold users degrade
    for u in users:
        rec = idx[u][idx[u] >= 0]
        assert not train_mask[u, rec].any(), "seen item recommended"
        if not flags[u]:
            assert (ds.item_city[rec] == ds.user_city[u]).all(), "out-of-city rec"


# ----------------------------------------------------------- online refresh
def test_online_refresh_decreases_loss_on_streamed_checkins():
    ds, nbr, cfg, state = _world(epochs=4)
    index = index_from_dataset(ds)
    eng = ServingEngine(state, index, ServingConfig(microbatch=16, k=5),
                        train=ds.train, nbr=nbr, dmf_cfg=cfg)
    events = ds.test[: min(30, len(ds.test))]
    before = dmf.test_loss(eng.state, events)
    report = eng.ingest(events, OnlineConfig(batch_cap=128, steps=3))
    after = dmf.test_loss(eng.state, events)
    assert after < before, (before, after)
    assert report.n_events == len(events)
    # served view and seen-filter track the refresh
    np.testing.assert_allclose(
        np.asarray(eng.V), np.asarray(eng.state.P + eng.state.Q), atol=0)
    assert np.asarray(eng.seen)[events[:, 0], events[:, 1]].all()


def test_online_refresh_touches_only_neighbor_table_receivers():
    """Acceptance: a refresh writes U/Q only for affected users and P only
    for their neighbor-table receivers; everyone else is bit-identical."""
    ds, nbr, cfg, state = _world(epochs=2)
    U0 = np.asarray(state.U).copy()
    P0 = np.asarray(state.P).copy()
    Q0 = np.asarray(state.Q).copy()
    events = ds.test[:12]
    new_state, report = online_refresh(
        state, nbr, events, cfg, OnlineConfig(batch_cap=64, steps=2))
    affected = set(report.affected_users.tolist())
    touched = set(report.touched_users.tolist())
    assert affected == set(np.unique(events[:, 0]).tolist())
    assert affected <= touched
    # receivers come from the positive-weight neighbor table rows
    wall = np.asarray(nbr.wgt)
    iall = np.asarray(nbr.idx)
    expect_recv = set()
    for u in affected:
        expect_recv |= set(iall[u][wall[u] > 0].tolist())
    assert touched == affected | expect_recv
    dU = np.flatnonzero(np.abs(np.asarray(new_state.U) - U0).max(1) > 0)
    dQ = np.flatnonzero(np.abs(np.asarray(new_state.Q) - Q0).max((1, 2)) > 0)
    dP = np.flatnonzero(np.abs(np.asarray(new_state.P) - P0).max((1, 2)) > 0)
    assert set(dU.tolist()) <= affected
    assert set(dQ.tolist()) <= affected
    assert set(dP.tolist()) <= touched
    # untouched rows are bit-identical, not just close
    untouched = sorted(set(range(ds.n_users)) - touched)
    np.testing.assert_array_equal(np.asarray(new_state.P)[untouched],
                                  P0[untouched])


def test_online_refresh_empty_events_noop():
    ds, nbr, cfg, state = _world(epochs=1)
    new_state, report = online_refresh(
        state, nbr, np.empty((0, 2), np.int64), cfg)
    assert report.n_events == 0 and report.n_batches == 0
    np.testing.assert_array_equal(np.asarray(new_state.U), np.asarray(state.U))


def test_engine_ingest_duplicate_events_in_one_window():
    """The same (user, item) check-in repeated inside one refresh window:
    the refresh treats each occurrence as an event (order-free sum of
    per-rating SGD contributions — heavier pull, same receivers), the
    seen-filter sets once, and the engine never recommends the item again."""
    ds, nbr, cfg, state = _world(epochs=4)
    index = index_from_dataset(ds)
    eng = ServingEngine(state, index, ServingConfig(microbatch=16, k=5),
                        train=ds.train, nbr=nbr, dmf_cfg=cfg)
    base = ds.test[:4]
    events = np.concatenate([base, base, base[:2]])   # dups in one window
    report = eng.ingest(events, OnlineConfig(batch_cap=64, steps=1))
    assert report.n_events == len(events)
    np.testing.assert_array_equal(
        report.affected_users, np.unique(base[:, 0]))
    # served view stays consistent with the refreshed factors
    np.testing.assert_array_equal(
        np.asarray(eng.V), np.asarray(eng.state.P + eng.state.Q))
    assert np.asarray(eng.seen)[base[:, 0], base[:, 1]].all()
    _, recs = eng.recommend(np.unique(base[:, 0]))
    for row, u in zip(recs, np.unique(base[:, 0])):
        own = base[base[:, 0] == u, 1]
        assert not set(own.tolist()) & set(row[row >= 0].tolist())


def test_engine_ingest_empty_event_stream():
    ds, nbr, cfg, state = _world(epochs=2)
    index = index_from_dataset(ds)
    eng = ServingEngine(state, index, ServingConfig(microbatch=16, k=5),
                        train=ds.train, nbr=nbr, dmf_cfg=cfg)
    V0 = np.asarray(eng.V).copy()
    seen0 = np.asarray(eng.seen).copy()
    report = eng.ingest(np.empty((0, 2), np.int64))
    assert report.n_events == 0 and report.n_batches == 0
    assert len(report.affected_users) == 0
    np.testing.assert_array_equal(np.asarray(eng.V), V0)
    np.testing.assert_array_equal(np.asarray(eng.seen), seen0)
    vals, recs = eng.recommend(np.arange(8))          # still serves
    assert recs.shape == (8, 5)


def test_engine_ingest_user_in_truncated_bucket_keeps_index_intact():
    """Events for users whose city bucket is AT CAPACITY (city > cap,
    priority-truncated): ingest must refresh factors/seen only — the
    candidate index is immutable and must come out bit-identical, and
    recommendations stay inside the truncated bucket and unseen."""
    ds = synthetic_poi.generate(synthetic_poi.POIDatasetConfig(
        n_users=60, n_items=300, n_ratings=900, n_cities=2, seed=5))
    gcfg = graph.GraphConfig(n_neighbors=2, walk_length=2)
    W = graph.build_adjacency(ds.user_coords, ds.user_city, gcfg)
    nbr = graph.walk_neighbor_table(W, gcfg)
    cfg = dmf.DMFConfig(n_users=ds.n_users, n_items=ds.n_items, dim=6,
                        beta=0.1, gamma=0.01, batch_size=64)
    res = dmf.fit(cfg, ds.train, nbr, epochs=3)
    index = index_from_dataset(ds, cap=128)           # both cities > 128
    assert index.n_truncated_buckets >= 1
    full_users = np.flatnonzero(~index.user_fits())
    assert len(full_users) > 0
    items0 = index.bucket_items.copy()
    sizes0 = index.bucket_size.copy()
    eng = ServingEngine(res.state, index, ServingConfig(microbatch=16, k=5),
                        train=ds.train, nbr=nbr, dmf_cfg=cfg)
    rng = np.random.default_rng(9)
    u = full_users[: 6]
    events = np.stack([u, rng.integers(0, ds.n_items, len(u))], 1)
    eng.ingest(events, OnlineConfig(batch_cap=64, steps=2))
    # the index is untouched — capacity pressure cannot corrupt it
    np.testing.assert_array_equal(eng.index.bucket_items, items0)
    np.testing.assert_array_equal(eng.index.bucket_size, sizes0)
    assert eng.index.cap == 128
    # and serving those users stays bucket-constrained and seen-filtered
    _, recs = eng.recommend(u)
    seen = np.asarray(eng.seen)
    for row, uu in zip(recs, u):
        bucket = set(items0[index.user_bucket[uu]].tolist()) - {-1}
        got = row[row >= 0]
        assert set(got.tolist()) <= bucket
        assert not seen[uu, got].any()


# --------------------------------------------- graceful degradation fallback
def _pop_slate(seen, k):
    counts = np.asarray(seen).astype(bool).sum(axis=0)
    items = np.argsort(-counts, kind="stable")[:k].astype(np.int32)
    vals = (counts[items] / max(int(counts.max()), 1)).astype(np.float32)
    return vals, items


def test_fallback_unknown_and_cold_users_get_popularity_slate():
    ds, nbr, cfg, state = _world()
    index = index_from_dataset(ds)
    seen = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    cold = 7
    seen[cold] = False                       # a user with zero interactions
    eng = ServingEngine(state, index, ServingConfig(microbatch=16, k=5),
                        seen=seen)
    normal = int(np.flatnonzero(seen.any(1))[0])
    users = np.asarray([cold, ds.n_users + 5, -1, normal])
    vals, idx, flags = eng.recommend(users, return_flags=True)
    np.testing.assert_array_equal(flags, [True, True, True, False])
    pv, pi = _pop_slate(seen, 5)
    for r in range(3):                       # flagged rows: popularity slate
        np.testing.assert_array_equal(idx[r], pi)
        np.testing.assert_array_equal(vals[r], pv)
    assert eng.stats.n_fallbacks == 3
    # the unflagged row is served from factors, identical to a clean batch
    v1, i1 = eng.recommend(np.asarray([normal]))
    np.testing.assert_array_equal(idx[3], i1[0])
    np.testing.assert_array_equal(vals[3], v1[0])


def test_fallback_empty_candidate_bucket():
    """A user whose home city has no POIs: the pruned path has nothing to
    score — fallback serves popularity; the dense (prune=False) path can
    still score full-J and must NOT flag such users."""
    ds, nbr, cfg, state = _world()
    item_city = np.where(np.arange(ds.n_items) % 2 == 0, 0, 2)  # city 1 empty
    user_city = np.zeros(ds.n_users, np.int64)
    user_city[3] = 1
    index = build_candidate_index(item_city, user_city, cap=128)
    assert (np.asarray(index.bucket_items[1]) == -1).all()
    eng = ServingEngine(state, index, ServingConfig(microbatch=16, k=5),
                        train=ds.train)
    vals, idx, flags = eng.recommend(np.asarray([3, 0]), return_flags=True)
    np.testing.assert_array_equal(flags, [True, False])
    pv, pi = _pop_slate(np.asarray(eng.seen), 5)
    np.testing.assert_array_equal(idx[0], pi)
    dense = ServingEngine(state, index,
                          ServingConfig(microbatch=16, k=5, prune=False),
                          train=ds.train)
    _, _, dflags = dense.recommend(np.asarray([3, 0]), return_flags=True)
    np.testing.assert_array_equal(dflags, [False, False])


def test_fallback_disabled_serves_factors_unflagged():
    ds, nbr, cfg, state = _world()
    index = index_from_dataset(ds)
    seen = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    cold = 7
    seen[cold] = False
    eng = ServingEngine(state, index,
                        ServingConfig(microbatch=16, k=5, fallback=False),
                        seen=seen)
    vals, idx, flags = eng.recommend(np.asarray([cold, 1]), return_flags=True)
    assert not flags.any() and eng.stats.n_fallbacks == 0
    # the cold row went through the factor path (whatever it scores), not
    # the popularity slate
    _, pi = _pop_slate(seen, 5)
    on = ServingEngine(state, index, ServingConfig(microbatch=16, k=5),
                       seen=seen)
    ov, oi, oflags = on.recommend(np.asarray([cold, 1]), return_flags=True)
    np.testing.assert_array_equal(oflags, [True, False])
    np.testing.assert_array_equal(oi[0], pi)
    np.testing.assert_array_equal(oi[1], idx[1])   # unflagged rows identical


def test_ingest_clears_cold_status_and_tracks_popularity():
    ds, nbr, cfg, state = _world(epochs=4)
    index = index_from_dataset(ds)
    seen = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    cold = 7
    seen[cold] = False
    eng = ServingEngine(state, index, ServingConfig(microbatch=16, k=5),
                        seen=seen, nbr=nbr, dmf_cfg=cfg)
    assert eng._fallback_mask(np.asarray([cold]))[0]
    counts0 = eng._item_counts.copy()
    j = int(np.asarray(index.bucket_items[index.user_bucket[cold]]).max())
    eng.ingest(np.asarray([[cold, j]], np.int64))
    # first check-in: no longer cold, served from factors now
    _, _, flags = eng.recommend(np.asarray([cold]), return_flags=True)
    assert not flags[0]
    # popularity ledger tracked the stream
    assert eng._item_counts[j] == counts0[j] + 1
    assert eng._item_counts.sum() == counts0.sum() + 1


@pytest.mark.sharded
def test_fallback_sharded_matches_single_shard():
    """Unknown ids are clamped to row 0 BEFORE dispatch (an out-of-range id
    would route to no shard) — sharded fallback == single-shard fallback."""
    ds, nbr, cfg, state = _world()
    index = index_from_dataset(ds)
    seen = metrics.masks_from_interactions(ds.n_users, ds.n_items, ds.train)
    seen[7] = False
    users = np.asarray([7, ds.n_users + 3, 0, 11, -2, 5])
    e1 = ServingEngine(state, index, ServingConfig(microbatch=8, k=5),
                       seen=seen)
    e2 = ServingEngine(state, index,
                       ServingConfig(microbatch=8, k=5, n_shards=2),
                       seen=seen)
    v1, i1, f1 = e1.recommend(users, return_flags=True)
    v2, i2, f2 = e2.recommend(users, return_flags=True)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(v1, v2)
    assert e2.stats.n_fallbacks == int(f1.sum()) > 0


def test_online_refresh_padded_rows_are_exact_noops():
    """batch_cap >> n_events: padded conf=0/valid=0 rows must contribute
    exactly nothing (regularizer pulls masked too)."""
    ds, nbr, cfg, state = _world(epochs=1, seed=3)
    # host copies: the refresh step donates its U/P/Q buffers
    U0, P0, Q0 = (np.asarray(x).copy() for x in (state.U, state.P, state.Q))
    events = ds.test[:5]

    def run(cap, seed=11):
        st = dmf.DMFState(jnp.asarray(U0), jnp.asarray(P0), jnp.asarray(Q0))
        new, _ = online_refresh(st, nbr, events, cfg,
                                OnlineConfig(batch_cap=cap, steps=1),
                                rng=np.random.default_rng(seed))
        return new

    sa, sb = run(cap=32), run(cap=512)   # same negative draws, 16x more pad
    np.testing.assert_array_equal(np.asarray(sa.U), np.asarray(sb.U))
    np.testing.assert_array_equal(np.asarray(sa.P), np.asarray(sb.P))
    np.testing.assert_array_equal(np.asarray(sa.Q), np.asarray(sb.Q))


# ------------------------------------------------- stream order & latency
@pytest.mark.sharded
def test_serve_stream_ordered_and_unordered_pinned():
    """Sharded serve_stream has two documented yield orders: the default
    follows the shard drain (per dispatch: shard 0's batch, then shard 1's),
    ordered=True reassembles strict arrival order. Pin BOTH, and pin every
    slate bitwise against the single-shard engine. fallback=False engines:
    the raw stream never applies popularity overwrites."""
    ds, nbr, cfg, state = _world()
    index = index_from_dataset(ds)
    users = np.random.default_rng(2).integers(0, ds.n_users, 37)
    ref = ServingEngine(state, index,
                        ServingConfig(microbatch=8, k=5, fallback=False),
                        train=ds.train)
    v_ref, i_ref = ref.recommend(users)
    slate = {int(u): j for j, u in enumerate(users)}   # user -> a ref row

    eng = ServingEngine(state, index,
                        ServingConfig(microbatch=8, k=5, n_shards=2,
                                      fallback=False), train=ds.train)
    got = list(eng.serve_stream(users, ordered=True))
    np.testing.assert_array_equal(
        np.concatenate([u for u, _, _ in got]), users)
    np.testing.assert_array_equal(
        np.concatenate([v for _, v, _ in got]), v_ref)
    np.testing.assert_array_equal(
        np.concatenate([i for _, _, i in got]), i_ref)

    eng2 = ServingEngine(state, index,
                         ServingConfig(microbatch=8, k=5, n_shards=2,
                                       fallback=False), train=ds.train)
    rows = eng2._rows
    flat_u, flat_v, flat_i = [], [], []
    for u, v, i in eng2.serve_stream(users):
        flat_u.extend(int(x) for x in u)
        flat_v.append(v)
        flat_i.append(i)
    # the default order is exactly the shard-queue drain order
    queues = [[int(u) for u in users if u // rows == d] for d in range(2)]
    offs, expected = [0, 0], []
    while any(o < len(q) for o, q in zip(offs, queues)):
        for d in range(2):
            take = queues[d][offs[d]:offs[d] + 8]
            offs[d] += len(take)
            expected.extend(take)
    assert flat_u == expected
    flat_v, flat_i = np.concatenate(flat_v), np.concatenate(flat_i)
    for j, u in enumerate(flat_u):       # same user => identical slate
        np.testing.assert_array_equal(flat_v[j], v_ref[slate[u]])
        np.testing.assert_array_equal(flat_i[j], i_ref[slate[u]])


def test_latency_accounting_is_request_level():
    """EngineStats charges arrival->completion per REQUEST: a request in the
    w-th microbatch of a drain pays for every dispatch before it. The old
    per-dispatch numbers survive as the dispatch_* diagnostics."""
    ds, nbr, cfg, state = _world()
    index = index_from_dataset(ds)
    eng = ServingEngine(state, index, ServingConfig(microbatch=8, k=5),
                        train=ds.train)
    eng.recommend(np.arange(24) % ds.n_users)
    st = eng.stats
    assert st.n_requests == 24 and len(st.request_seconds) == 24
    assert st.n_dispatches == 3 and len(st.dispatch_seconds) == 3
    # the last microbatch's requests paid for all three dispatches
    assert max(st.request_seconds) >= sum(st.dispatch_seconds)
    assert st.request_seconds == sorted(st.request_seconds)
    p, d = st.latency_percentiles(), st.dispatch_latency_percentiles()
    assert set(p) == {"p50_ms", "p95_ms", "p99_ms"} == set(d)
    assert p["p99_ms"] >= d["p99_ms"]

    eng2 = ServingEngine(state, index, ServingConfig(microbatch=8, k=5),
                        train=ds.train)
    *_, dt = eng2.serve_microbatch(np.arange(5))
    assert eng2.stats.request_seconds == [dt] * 5
    assert eng2.stats.n_requests == 5 and eng2.stats.n_dispatches == 1
