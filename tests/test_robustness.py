"""Fault-tolerant decentralized training (src/repro/robustness/): churn
schedule determinism, no-churn bit-exactness with the PR 1-4 paths
(single-device and every shard count), the offline bit-freeze /
message-loss / late-join contracts, stale-gradient DelayRing delivery
semantics, sharded-churn equivalence, crash-resume bit-identity (DP on),
and the dropout+staleness degradation envelope (DESIGN.md §10)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dmf, graph
from repro.data import synthetic_poi
from repro.robustness import (ChurnConfig, ChurnPlan, DelayRing, no_churn,
                              recovery)

pytestmark = pytest.mark.robustness

EPOCHS = 5


def _world(n_users=80, n_items=50, n_ratings=600, seed=0):
    ds = synthetic_poi.generate(synthetic_poi.POIDatasetConfig(
        n_users=n_users, n_items=n_items, n_ratings=n_ratings, n_cities=4,
        seed=seed))
    gcfg = graph.GraphConfig(n_neighbors=2, walk_length=3)
    W = graph.build_adjacency(ds.user_coords, ds.user_city, gcfg)
    return ds, graph.walk_neighbor_table(W, gcfg)


def _cfg(ds, **kw):
    base = dict(n_users=ds.n_users, n_items=ds.n_items, dim=6,
                batch_size=64, beta=0.1, gamma=0.01)
    base.update(kw)
    return dmf.DMFConfig(**base)


def _assert_states_equal(a, b, **tol):
    for name in ("U", "P", "Q"):
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        if tol:
            np.testing.assert_allclose(x, y, **tol, err_msg=name)
        else:
            np.testing.assert_array_equal(x, y, err_msg=name)


# ---------------------------------------------------------------------------
# Schedule compilation
# ---------------------------------------------------------------------------
def test_churn_compile_deterministic_and_seed_keyed():
    cc = ChurnConfig(dropout=0.2, session_alpha=1.5, late_frac=0.2,
                     delay_classes=(0, 1, 2), seed=7)
    a, b = cc.compile(64, 20), cc.compile(64, 20)
    np.testing.assert_array_equal(a.online, b.online)
    np.testing.assert_array_equal(a.delay, b.delay)
    np.testing.assert_array_equal(a.join_epoch, b.join_epoch)
    c = dataclasses.replace(cc, seed=8).compile(64, 20)
    assert (a.online != c.online).any()
    assert a.k_max == 2 and 0.0 < a.participation_rate < 1.0
    assert not a.is_trivial()
    # late joiners are offline (stateless) strictly before their join epoch
    late = np.flatnonzero(a.join_epoch > 0)
    assert late.size > 0
    for u in late:
        assert not a.online[: a.join_epoch[u], u].any()


def test_no_churn_plan_is_trivial():
    plan = no_churn(16, 4)
    assert plan.is_trivial()
    assert plan.participation_rate == 1.0 and plan.k_max == 0
    # trivial plan ⇒ no ring allocated at all
    assert DelayRing.create(plan.k_max, 128, 6) is None


def test_epoch_row_masks_semantics():
    online = np.ones((3, 6), bool)
    online[1, 2] = False
    delay = np.asarray([0, 1, 0, 2, 0, 0], np.int32)
    plan = ChurnPlan(online=online, delay=delay,
                     join_epoch=np.zeros(6, np.int32))
    ui = np.asarray([[0, 1, 2, 3]])
    on, sender_on, prop_now, due = plan.epoch_row_masks(1, ui)
    np.testing.assert_array_equal(on, online[1])
    np.testing.assert_array_equal(sender_on, [[True, True, False, True]])
    # stragglers (delay>0) never propagate now; offline rows never at all
    np.testing.assert_array_equal(prop_now, [[True, False, False, False]])
    # due = t + delay for online stragglers only, -1 otherwise
    np.testing.assert_array_equal(due, [[-1, 2, -1, 3]])


# ---------------------------------------------------------------------------
# No-churn ⇒ bit-exact with the fault-free paths (acceptance)
# ---------------------------------------------------------------------------
def test_no_churn_bitexact_single_device():
    ds, nbr = _world()
    plain = dmf.fit(_cfg(ds), ds.train, nbr, epochs=EPOCHS, test=ds.test)
    churn = dmf.fit(_cfg(ds), ds.train, nbr, epochs=EPOCHS, test=ds.test,
                    churn=ChurnConfig())
    assert churn.train_losses == plain.train_losses
    assert churn.test_losses == plain.test_losses
    _assert_states_equal(churn.state, plain.state)


def test_no_churn_bitexact_with_dp():
    """The trivial plan composes with the DP mechanism bit-exactly too:
    same rng protocol ⇒ same per-epoch noise seeds ⇒ same noise."""
    ds, nbr = _world()
    cfg = _cfg(ds, dp_sigma=0.5, dp_clip=1.0, dp_seed=3)
    plain = dmf.fit(cfg, ds.train, nbr, epochs=3)
    churn = dmf.fit(cfg, ds.train, nbr, epochs=3, churn=no_churn(ds.n_users, 3))
    assert churn.train_losses == plain.train_losses
    _assert_states_equal(churn.state, plain.state)
    assert churn.privacy["eps_max"] == plain.privacy["eps_max"]


@pytest.mark.sharded
def test_no_churn_bitexact_sharded():
    ds, nbr = _world()
    for n_shards in (1, 2, 4, 8):
        cfg = _cfg(ds, n_shards=n_shards)
        plain = dmf.fit(cfg, ds.train, nbr, epochs=EPOCHS)
        churn = dmf.fit(cfg, ds.train, nbr, epochs=EPOCHS,
                        churn=ChurnConfig())
        assert churn.train_losses == plain.train_losses, n_shards
        _assert_states_equal(churn.state, plain.state)


# ---------------------------------------------------------------------------
# Offline ⇒ bit-frozen; rejoin catches up through the protocol
# ---------------------------------------------------------------------------
def test_offline_learner_rows_bit_frozen():
    """Learners offline in an epoch neither release nor receive: their U, Q
    AND P rows come out bitwise identical, while online learners train."""
    ds, nbr = _world()
    cfg = _cfg(ds)
    online = np.ones((2, ds.n_users), bool)
    offline = np.asarray([3, 11, 40, 79])
    online[0, offline] = False
    plan = ChurnPlan(online=online, delay=np.zeros(ds.n_users, np.int32),
                     join_epoch=np.zeros(ds.n_users, np.int32))
    rng = np.random.default_rng(cfg.seed)
    state0 = dmf.init_state(cfg, rng)
    before = {k: np.asarray(getattr(state0, k)).copy() for k in ("U", "P", "Q")}
    state1, loss = dmf.train_epoch(state0, nbr, ds.train, cfg, rng,
                                   t=0, plan=plan, ring=None)
    assert np.isfinite(loss)
    for name in ("U", "P", "Q"):
        after = np.asarray(getattr(state1, name))
        np.testing.assert_array_equal(after[offline], before[name][offline],
                                      err_msg=f"offline {name} rows moved")
    # the fleet minus the offline set still trained
    U1 = np.asarray(state1.U).copy()   # the next epoch donates state1's buffers
    assert (U1 != before["U"]).any()
    # rejoin: epoch 1 (everyone online) moves the previously-frozen rows
    state2, _ = dmf.train_epoch(state1, nbr, ds.train, cfg, rng,
                                t=1, plan=plan, ring=None)
    moved = np.asarray([
        (np.asarray(state2.U)[u] != U1[u]).any() for u in offline])
    assert moved.any(), "rejoined learners never caught back up"


def test_late_joiner_stateless_until_join_epoch():
    ds, nbr = _world()
    cfg = _cfg(ds)
    cc = ChurnConfig(late_frac=0.2, late_by=0.5, seed=5)
    plan = cc.compile(ds.n_users, EPOCHS)
    late = np.flatnonzero(plan.join_epoch > 0)
    assert late.size > 0
    rng = np.random.default_rng(cfg.seed)
    state = dmf.init_state(cfg, rng)
    init = {k: np.asarray(getattr(state, k)).copy() for k in ("U", "P", "Q")}
    for t in range(EPOCHS):
        for u in late[plan.join_epoch[late] > t]:
            # not joined yet ⇒ still exactly the init rows
            np.testing.assert_array_equal(np.asarray(state.U)[u], init["U"][u])
            np.testing.assert_array_equal(np.asarray(state.Q)[u], init["Q"][u])
            np.testing.assert_array_equal(np.asarray(state.P)[u], init["P"][u])
        state, _ = dmf.train_epoch(state, nbr, ds.train, cfg, rng,
                                   t=t, plan=plan, ring=None)


# ---------------------------------------------------------------------------
# Stale exchange: DelayRing delivery semantics
# ---------------------------------------------------------------------------
def _straggler_world():
    """A world where ONLY user s rates: the epoch stream carries s's
    messages exclusively, so neighbor-row movement isolates the exchange."""
    ds, nbr = _world()
    wgt = np.asarray(nbr.wgt)
    idx = np.asarray(nbr.idx)
    # a sender with at least one real (positive-weight, non-self) receiver
    s = next(u for u in range(ds.n_users)
             if ((wgt[u] > 0) & (idx[u] != u)).any())
    receivers = np.unique(idx[s][(wgt[s] > 0) & (idx[s] != s)])
    train = ds.train[ds.train[:, 0] == s]
    if len(train) < 8:   # top up so the stream fills at least two batches
        items = np.random.default_rng(0).choice(ds.n_items, 8, replace=False)
        train = np.stack([np.full(8, s), items], 1).astype(ds.train.dtype)
    cfg = _cfg(ds, batch_size=16)
    return ds, nbr, cfg, s, receivers, train


def _run_epochs(cfg, nbr, train, plan, epochs):
    rng = np.random.default_rng(cfg.seed)
    state = dmf.init_state(cfg, rng)
    nb = (len(train) * (1 + cfg.neg_samples)) // cfg.batch_size
    ring = DelayRing.create(plan.k_max, nb * cfg.batch_size, cfg.dim)
    hist = [np.asarray(state.P).copy()]
    for t in range(epochs):
        state, _ = dmf.train_epoch(state, nbr, train, cfg, rng, t=t,
                                   plan=plan, ring=ring)
        hist.append(np.asarray(state.P).copy())
    return hist


def test_straggler_messages_land_exactly_k_epochs_late():
    ds, nbr, cfg, s, receivers, train = _straggler_world()
    delay = np.zeros(ds.n_users, np.int32)
    delay[s] = 2
    plan = ChurnPlan(online=np.ones((4, ds.n_users), bool), delay=delay,
                     join_epoch=np.zeros(ds.n_users, np.int32))
    hist = _run_epochs(cfg, nbr, train, plan, 4)
    # epochs 0 and 1: s's neighbor scatters are in flight — receiver P rows
    # bitwise untouched (s's own rows DO move: local compute is never late)
    np.testing.assert_array_equal(hist[1][receivers], hist[0][receivers])
    np.testing.assert_array_equal(hist[2][receivers], hist[0][receivers])
    assert (hist[1][s] != hist[0][s]).any()
    # epoch 2 starts by delivering epoch 0's messages (due = 0 + 2)
    assert (hist[3][receivers] != hist[2][receivers]).any()


def test_message_to_offline_receiver_is_lost_not_queued():
    ds, nbr, cfg, s, receivers, train = _straggler_world()
    delay = np.zeros(ds.n_users, np.int32)
    delay[s] = 1
    online = np.ones((3, ds.n_users), bool)
    online[1, receivers] = False     # offline exactly when delivery is due
    online[1:, s] = False            # sender quiet after epoch 0: the only
    plan = ChurnPlan(online=online, delay=delay,  # in-flight message is t=0's
                     join_epoch=np.zeros(ds.n_users, np.int32))
    hist = _run_epochs(cfg, nbr, train, plan, 3)
    # due==1 never matches any later epoch: the message is gone for good,
    # not delivered late at t=2 when the receivers come back
    np.testing.assert_array_equal(hist[2][receivers], hist[0][receivers])
    np.testing.assert_array_equal(hist[3][receivers], hist[0][receivers])
    # control: same schedule with the receivers online delivers at t=1
    plan_on = ChurnPlan(online=np.ones((3, ds.n_users), bool), delay=delay,
                        join_epoch=np.zeros(ds.n_users, np.int32))
    hist_on = _run_epochs(cfg, nbr, train, plan_on, 2)
    assert (hist_on[2][receivers] != hist_on[1][receivers]).any()


def test_delay_ring_slot_reuse_is_collision_free():
    ring = DelayRing.create(2, 8, 4)
    assert ring.slots == 2
    gp = jnp.ones((8, 4))
    ui = np.arange(8, dtype=np.int32)
    for t in range(5):
        ring.write(t, gp * (t + 1), ui, ui, np.full(8, t + 2, np.int32))
    # slot t%2 holds the LATEST write for that parity; older dues are gone
    np.testing.assert_array_equal(ring.due[0], np.full(8, 4 + 2))  # t=4
    np.testing.assert_array_equal(ring.due[1], np.full(8, 3 + 2))  # t=3
    np.testing.assert_array_equal(np.asarray(ring.gp[0]), 5.0 * np.ones((8, 4)))


# ---------------------------------------------------------------------------
# Sharded churn == single-device churn (one SPMD dispatch per epoch)
# ---------------------------------------------------------------------------
@pytest.mark.sharded
def test_sharded_churn_matches_single_device():
    ds, nbr = _world()
    cc = ChurnConfig(dropout=0.2, delay_classes=(0, 1, 2), late_frac=0.1,
                     seed=4)
    ref = dmf.fit(_cfg(ds), ds.train, nbr, epochs=EPOCHS, churn=cc)
    for n_shards in (2, 4, 8):
        got = dmf.fit(_cfg(ds, n_shards=n_shards), ds.train, nbr,
                      epochs=EPOCHS, churn=cc)
        np.testing.assert_allclose(ref.train_losses, got.train_losses,
                                   atol=1e-7, err_msg=str(n_shards))
        _assert_states_equal(got.state, ref.state, rtol=0, atol=1e-5)


@pytest.mark.sharded
def test_sharded_churn_with_dp_matches_single_device():
    """Churn, staleness AND the DP mechanism compose shard-invariantly:
    counter-keyed noise + shard-invariant ring delivery."""
    ds, nbr = _world()
    cc = ChurnConfig(dropout=0.2, delay_classes=(0, 1), seed=4)
    cfg = _cfg(ds, dp_sigma=0.5, dp_clip=1.0, dp_seed=3)
    ref = dmf.fit(cfg, ds.train, nbr, epochs=EPOCHS, churn=cc)
    got = dmf.fit(dataclasses.replace(cfg, n_shards=4), ds.train, nbr,
                  epochs=EPOCHS, churn=cc)
    np.testing.assert_allclose(ref.train_losses, got.train_losses, atol=1e-7)
    _assert_states_equal(got.state, ref.state, rtol=0, atol=1e-5)
    assert got.privacy["eps_max"] == pytest.approx(ref.privacy["eps_max"])


# ---------------------------------------------------------------------------
# Recovery: resume-after-crash is bit-identical (acceptance)
# ---------------------------------------------------------------------------
def test_resume_bit_identical_with_dp_and_churn(tmp_path):
    ds, nbr = _world()
    cfg = _cfg(ds, dp_sigma=0.7, dp_clip=1.0, dp_seed=2)
    cc = ChurnConfig(dropout=0.2, delay_classes=(0, 1, 2), late_frac=0.1,
                     seed=9)
    full = dmf.fit(cfg, ds.train, nbr, epochs=EPOCHS, test=ds.test, churn=cc,
                   checkpoint_dir=tmp_path, checkpoint_every=2)
    # "crash" after epoch 2, resume from its snapshot — every field of the
    # run (factors, losses, ε ledger) must come out bit-identical
    resumed = dmf.fit(cfg, ds.train, nbr, epochs=EPOCHS, test=ds.test,
                      churn=cc, resume_from=tmp_path / "step_2")
    assert resumed.train_losses == full.train_losses
    assert resumed.test_losses == full.test_losses
    _assert_states_equal(resumed.state, full.state)
    assert resumed.privacy == full.privacy


def test_resume_from_root_picks_latest_step(tmp_path):
    ds, nbr = _world()
    cfg = _cfg(ds)
    full = dmf.fit(cfg, ds.train, nbr, epochs=4,
                   checkpoint_dir=tmp_path, checkpoint_every=1)
    assert recovery.resolve_step_dir(tmp_path).name == "step_4"
    resumed = dmf.fit(cfg, ds.train, nbr, epochs=4, resume_from=tmp_path)
    # latest snapshot is the finished run: nothing left to train
    assert resumed.train_losses == full.train_losses
    _assert_states_equal(resumed.state, full.state)


@pytest.mark.sharded
def test_resume_sharded_and_across_mesh_widths(tmp_path):
    """Snapshots are unpadded (global learner axis): a sharded run resumes
    bit-identically, and the SAME snapshot restores onto a different mesh
    width within the cross-shard tolerance."""
    ds, nbr = _world()
    cc = ChurnConfig(dropout=0.2, delay_classes=(0, 1), seed=4)
    cfg2 = _cfg(ds, n_shards=2)
    full = dmf.fit(cfg2, ds.train, nbr, epochs=EPOCHS, churn=cc,
                   checkpoint_dir=tmp_path, checkpoint_every=2)
    resumed = dmf.fit(cfg2, ds.train, nbr, epochs=EPOCHS, churn=cc,
                      resume_from=tmp_path / "step_2")
    assert resumed.train_losses == full.train_losses
    _assert_states_equal(resumed.state, full.state)
    # mesh-width switch mid-run: 2-shard snapshot, 4-shard continuation
    wider = dmf.fit(_cfg(ds, n_shards=4), ds.train, nbr, epochs=EPOCHS,
                    churn=cc, resume_from=tmp_path / "step_2")
    np.testing.assert_allclose(wider.train_losses, full.train_losses,
                               atol=1e-6)
    _assert_states_equal(wider.state, full.state, rtol=0, atol=1e-5)


def test_resume_ring_mismatch_raises(tmp_path):
    ds, nbr = _world()
    cfg = _cfg(ds)
    dmf.fit(cfg, ds.train, nbr, epochs=2, churn=ChurnConfig(),  # k_max=0
            checkpoint_dir=tmp_path, checkpoint_every=2)
    with pytest.raises(ValueError, match="has_ring"):
        dmf.fit(cfg, ds.train, nbr, epochs=2,
                churn=ChurnConfig(delay_classes=(0, 1)),        # wants a ring
                resume_from=tmp_path / "step_2")


# ---------------------------------------------------------------------------
# Degradation envelope: bounded churn ⇒ bounded loss gap, still converging
# ---------------------------------------------------------------------------
def test_degradation_envelope_dropout_and_staleness():
    ds, nbr = _world()
    cfg = _cfg(ds)
    free = dmf.fit(cfg, ds.train, nbr, epochs=8)
    cc = ChurnConfig(dropout=0.3, delay_classes=(0, 1, 2), seed=1)
    hit = dmf.fit(cfg, ds.train, nbr, epochs=8, churn=cc)
    # still optimizing, loss finite every epoch
    assert all(np.isfinite(hit.train_losses))
    assert hit.train_losses[-1] < hit.train_losses[0]
    # pinned envelope: dropout ≤ 0.3 + staleness ≤ 2 costs a bounded final-
    # loss gap vs the fault-free run (per-realized-row losses: comparable)
    gap = abs(hit.train_losses[-1] - free.train_losses[-1])
    assert gap <= 0.5 * free.train_losses[-1], (
        hit.train_losses[-1], free.train_losses[-1])
