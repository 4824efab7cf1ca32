"""Online factor refresh: streamed check-ins update served factors in place.

Following "Practical Privacy Preserving POI Recommendation" (Chen et al.)
and "Decentralized Collaborative Learning Framework for Next POI
Recommendation" (Long et al.), on-device inference comes with *incremental*
refresh: when user i checks in at POI j, the learner applies the paper's
Eqs. 9-11 local SGD step for (i, j) — plus a few sampled negatives, exactly
the training-time objective — and ships only the global-factor gradient
∂L/∂p^i_j to its ≤D-hop `walk_neighbor_table` receivers. Ratings never
leave the user; the privacy contract is unchanged from training (the same
`core/dmf._sparse_batch_update_messages` executes the step).

Locality guarantee (unit-tested): one refresh touches
  * U rows:  only the users with new check-ins ("affected"),
  * Q rows:  only affected users,
  * P rows:  only the union of the affected users' neighbor-table receivers
             (which includes the senders themselves),
and nothing else — the served population keeps its factors bit-identical.

Events are padded to a fixed dispatch shape (``OnlineConfig.batch_cap``)
so every refresh reuses one compiled step; padded rows carry conf=0 and
valid=0 and contribute exactly nothing (see `dmf._step_deltas`). The
U/P/Q buffers are donated to the step — refresh is in-place at the XLA
level, no copy of the (I, J, K) factors per event batch.

DP (``cfg.dp``): the refresh runs the same privacy/mechanism.py clip+noise
pass over each outgoing gradient message as training — the online channel
is not a side door around the mechanism. Each `online_refresh` call draws
one fresh mechanism seed from its rng (DP off: no draw, stream unchanged)
and keys noise by the row's position in the refresh stream.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dmf
from repro.core import graph as graph_lib


@dataclasses.dataclass(frozen=True)
class OnlineConfig:
    batch_cap: int = 256    # fixed event-batch shape (events + negatives)
    steps: int = 4          # local SGD passes over the event batch
    neg_samples: int = 3    # m fresh unobserved negatives per check-in


@dataclasses.dataclass
class RefreshReport:
    affected_users: np.ndarray   # unique users with new check-ins
    touched_users: np.ndarray    # affected ∪ their neighbor-table receivers
    losses: list[float]          # per-step batch loss on the event batch
    n_events: int
    n_batches: int


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0, 1, 2))
def _refresh_step(U, P, Q, nbr_idx, nbr_wgt, ui, vj, r, conf, valid, rid,
                  dp_seed, cfg):
    U, P, Q, loss, _ = dmf._sparse_batch_update_messages(
        U, P, Q, nbr_idx, nbr_wgt, ui, vj, r, conf, cfg, valid=valid,
        rid=rid, dp_seed=dp_seed,
    )
    return U, P, Q, loss


def _event_batches(events: np.ndarray, cfg: dmf.DMFConfig, ocfg: OnlineConfig,
                   rng: np.random.Generator, rid_offset: int = 0):
    """Pack check-ins + per-event negatives into fixed-shape (cap,) batches.

    Negatives are freshly sampled unobserved items with confidence 1/m via
    the training-time `dmf.sample_with_negatives` (same objective by
    construction) — without them a refresh would only push scores up and
    drift the ranking calibration. ``rid_offset`` shifts the rows' DP
    noise-key ids so successive local passes over the same events never
    reuse a noise draw."""
    ui, vj, r, conf = dmf.sample_with_negatives(
        events, cfg.n_items, ocfg.neg_samples, rng)

    cap = ocfg.batch_cap
    total = len(ui)
    for s in range(0, total, cap):
        sl = slice(s, min(s + cap, total))
        b = sl.stop - sl.start
        pad = cap - b
        yield (
            jnp.asarray(np.pad(ui[sl], (0, pad)).astype(np.int32)),
            jnp.asarray(np.pad(vj[sl], (0, pad)).astype(np.int32)),
            jnp.asarray(np.pad(r[sl], (0, pad)).astype(np.float32)),
            jnp.asarray(np.pad(conf[sl], (0, pad)).astype(np.float32)),
            jnp.asarray((np.arange(cap) < b).astype(np.float32)),
            jnp.asarray(rid_offset + s + np.arange(cap, dtype=np.int32)),
        )


def touched_from_events(events: np.ndarray,
                        nbr: graph_lib.NeighborTable) -> tuple[np.ndarray, np.ndarray]:
    """(affected, touched): the users whose factors a refresh may write.
    Touched = affected ∪ {their positive-weight neighbor-table receivers};
    padded table slots (weight 0) are scatter no-ops and don't count."""
    affected = np.unique(np.asarray(events)[:, 0]).astype(np.int64)
    idx = np.asarray(nbr.idx)[affected]
    wgt = np.asarray(nbr.wgt)[affected]
    receivers = np.unique(idx[wgt > 0])
    touched = np.union1d(affected, receivers)
    return affected, touched


def online_refresh(
    state: dmf.DMFState,
    nbr: graph_lib.NeighborTable,
    events: np.ndarray,            # (n, 2) int (user, item) new check-ins
    cfg: dmf.DMFConfig,
    ocfg: OnlineConfig = OnlineConfig(),
    rng: np.random.Generator | None = None,
) -> tuple[dmf.DMFState, RefreshReport]:
    """Apply the Eq. 9-11 local step for the affected users and scatter the
    global-factor gradients to their neighbor-table receivers. Returns the
    refreshed state and a locality report.

    **Takes ownership of ``state``'s buffers**: they are donated to the
    refresh step (no (I, J, K) copy per event batch) and deleted by XLA —
    reading the old ``state`` afterwards raises. Pass a copy
    (``jnp.array(x)`` per field) if the caller still needs it;
    `ServingEngine` copies once at construction for exactly this reason."""
    events = np.asarray(events)
    if len(events) == 0:
        return state, RefreshReport(
            np.empty(0, np.int64), np.empty(0, np.int64), [], 0, 0)
    if cfg.dp and rng is None:
        # the fallback rng is freshly seeded from cfg.seed EVERY call: under
        # DP that would re-derive the same noise seed per refresh window,
        # and repeated noise cancels in update differences — the exact leak
        # the mechanism exists to prevent. A persistent stream is required
        # (ServingEngine.ingest holds one; pass your own otherwise).
        raise ValueError(
            "online_refresh with DP on needs an explicit persistent rng — "
            "the default would reuse the same noise stream every call")
    rng = rng or np.random.default_rng(cfg.seed)
    affected, touched = touched_from_events(events, nbr)

    dp_seed = 0
    if cfg.dp:
        from repro.privacy import mechanism
        dp_seed = mechanism.epoch_noise_seed(rng, cfg)
    dp_seed_j = jnp.asarray(dp_seed, jnp.int32)
    stream_len = len(events) * (1 + ocfg.neg_samples)

    U, P, Q = state.U, state.P, state.Q
    losses = []
    n_batches = 0
    for step in range(ocfg.steps):
        for ui, vj, r, conf, valid, rid in _event_batches(
                events, cfg, ocfg, rng, rid_offset=step * stream_len):
            U, P, Q, loss = _refresh_step(
                U, P, Q, nbr.idx, nbr.wgt, ui, vj, r, conf, valid, rid,
                dp_seed_j, cfg)
            losses.append(float(loss))
            n_batches += 1
    report = RefreshReport(
        affected_users=affected,
        touched_users=touched,
        losses=losses,
        n_events=int(len(events)),
        n_batches=n_batches,
    )
    return dmf.DMFState(U, P, Q), report
