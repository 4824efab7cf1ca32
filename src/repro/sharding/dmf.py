"""Learner-sharded DMF: Alg. 1 as SPMD over a ``learners`` mesh axis.

The paper frames DMF as "distributed learning with multi-learners (users)";
this module makes that literal at execution level: the learner axis of every
per-user buffer — U (I, K), P/Q (I, J, K), the neighbor table, the serving
engine's V/seen rows — is partitioned row-wise over an ``n_shards``-device
mesh, and one epoch is ONE SPMD dispatch (shard_map over the existing
`lax.scan` epoch). Item factors are *per-learner copies* already, so the
item axis needs no sharding — only learner-to-learner messages cross shard
boundaries, exactly like the paper's protocol.

Cross-shard propagation (Alg. 1 lines 13-15): each rating's global-factor
gradient ∂L/∂p^i_j must reach user i's ≤D-hop receivers, who may live on
other shards. `graph.partition_neighbor_table` pre-splits each sender row
of the (I, S) neighbor table by *destination shard* into an (I, n_shards, S)
schema, so a training step builds a fixed-shape outbox per destination —
   (weights (D, B, S), local receiver rows (D, B, S),
    gradients gp (D, B, K), item ids (D, B))
— and routes it with one `lax.all_to_all` per tensor. The receiving shard
scatter-adds ``-θ · w · gp`` into its local P rows. Weight-0 slots (receiver
on another shard, padded batch rows, padded table slots) scatter exactly
zero, so the sharded step applies precisely the same update mass as the
single-device sparse path (invariance suite: tests/test_dmf_sharded.py).

Privacy invariant (the paper's "only gradients ever leave a learner"): the
outbox is a pure function of (gp, static graph tables, item ids) — built by
`build_outbox`, which never sees ratings, u_i, or q^i. Ratings influence
other shards only through the gp messages; a learner's U/Q rows live only
on its home shard (tests/test_dmf_sharded.py::test_privacy_*).

Batch routing: the epoch's minibatch stream is the SAME stream the
single-device path samples (same rng), with each minibatch's rows routed
host-side to their user's home shard and padded to a fixed per-shard
capacity with valid=0 rows (exact no-ops, the `_sparse_batch_update`
convention). SGD batch semantics are unchanged — a minibatch's updates are
an order-free sum, so distributing its rows over shards is associativity,
not approximation (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dmf as dmf_lib
from repro.core import graph as graph_lib
from repro.core import metrics as metrics_lib

AXIS = "learners"

# jax.sharding.PartitionSpec under a second alias: inside the epoch body the
# name ``P`` is the item-factor buffer, so specs there use ``P_``.
P_ = P


def rows_per_shard(n_users: int, n_shards: int) -> int:
    return -(-n_users // n_shards)


def shard_row_slices(n_rows: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous [start, end) UNPADDED row ranges per shard under the same
    ceil-div layout as `rows_per_shard` (the trailing shards may be short or
    empty). The serving factor store's host-level row sharding
    (`serving/store.py shard_rows`) slices its HBM-resident slabs along
    these, so its request routing agrees with the SPMD engine's
    ``user // rows_per_shard`` rule."""
    rows = rows_per_shard(n_rows, n_shards)
    return [(min(d * rows, n_rows), min((d + 1) * rows, n_rows))
            for d in range(n_shards)]


@functools.lru_cache(maxsize=None)
def make_learner_mesh(n_shards: int) -> Mesh:
    """1-D ``learners`` mesh over the first n_shards local devices. On a CPU
    host, provision devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* jax
    initializes (tests/conftest.py does this for the test suite)."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise RuntimeError(
            f"need {n_shards} devices for the learner mesh, have {len(devs)}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} before jax initializes"
        )
    return Mesh(np.asarray(devs[:n_shards]), (AXIS,))


def pad_rows(x: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    """Zero-pad axis 0 up to n_rows (identity when already there)."""
    pad = n_rows - x.shape[0]
    if pad == 0:
        return x
    assert pad > 0, (x.shape, n_rows)
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))


def pad_state(state: dmf_lib.DMFState, n_rows: int) -> dmf_lib.DMFState:
    return dmf_lib.DMFState(
        U=pad_rows(state.U, n_rows),
        P=pad_rows(state.P, n_rows),
        Q=pad_rows(state.Q, n_rows),
    )


def unpad_state(state: dmf_lib.DMFState, n_users: int) -> dmf_lib.DMFState:
    """Slice the learner axis back to the real user count (gathers a sharded
    state onto the default device)."""
    if state.U.shape[0] == n_users:
        return state
    return dmf_lib.DMFState(
        U=jnp.asarray(state.U[:n_users]),
        P=jnp.asarray(state.P[:n_users]),
        Q=jnp.asarray(state.Q[:n_users]),
    )


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Static per-run sharding artifacts: the mesh and the
    destination-partitioned neighbor table. Build once via
    `make_shard_plan` and reuse across epochs (dmf.fit does)."""

    mesh: Mesh
    part: graph_lib.PartitionedNeighborTable
    n_shards: int

    @property
    def rows(self) -> int:
        return self.part.rows_per_shard

    @property
    def n_rows_padded(self) -> int:
        return self.part.rows_per_shard * self.n_shards


def make_shard_plan(nbr: graph_lib.NeighborTable, cfg: dmf_lib.DMFConfig) -> ShardPlan:
    part = graph_lib.partition_neighbor_table(nbr, cfg.n_shards, cfg.n_users)
    return ShardPlan(mesh=make_learner_mesh(cfg.n_shards), part=part,
                     n_shards=cfg.n_shards)


# ---------------------------------------------------------------------------
# Host-side batch routing: the single-device minibatch stream, with each
# batch's rows grouped by the sender's home shard.
# ---------------------------------------------------------------------------
def shard_batches(
    ui: np.ndarray, vj: np.ndarray, r: np.ndarray, conf: np.ndarray,
    n_shards: int, rows: int, cap_multiple: int = 32, extras=(),
):
    """Route (nb, B) minibatch rows to their user's home shard.

    Returns (ui_local, vj, r, conf, valid, rid), each (nb, n_shards, Bs)
    with Bs = max realized per-(batch, shard) row count rounded up to
    ``cap_multiple`` (a stable dispatch shape across epochs: the rounded max
    rarely moves, so the jitted epoch recompiles at most once or twice per
    run). Padded slots carry ui=0, conf=0, valid=0 — exact no-ops in the
    step. Row order inside a shard group preserves batch order, so
    n_shards=1 reproduces the single-device batch stream bit-for-bit.

    ``rid`` carries each routed row's GLOBAL stream position (batch·B +
    slot in the unsharded stream) — the DP mechanism keys its counter
    noise by it, which is what makes the noised sharded epoch invariant to
    the shard count (kernels/dp_noise.py).

    ``extras``: additional (nb, B) per-row float arrays (e.g. the churn
    path's fault gates) routed identically with fill 0, appended to the
    returned tuple in order.
    """
    nb, B = ui.shape
    shard = ui // rows                              # (nb, B)
    order = np.argsort(shard, axis=1, kind="stable")
    s_sorted = np.take_along_axis(shard, order, axis=1)
    counts = np.zeros((nb, n_shards), np.int64)
    np.add.at(counts, (np.repeat(np.arange(nb), B), shard.reshape(-1)), 1)
    Bs = int(-(-max(int(counts.max()), 1) // cap_multiple) * cap_multiple)
    start = np.concatenate(
        [np.zeros((nb, 1), np.int64), np.cumsum(counts, axis=1)[:, :-1]], axis=1)
    slot = np.arange(B)[None, :] - np.take_along_axis(start, s_sorted, axis=1)
    batch_ix = np.repeat(np.arange(nb), B)

    def route(x, fill=0):
        out = np.full((nb, n_shards, Bs), fill, x.dtype)
        xs = np.take_along_axis(x, order, axis=1)
        out[batch_ix, s_sorted.reshape(-1), slot.reshape(-1)] = xs.reshape(-1)
        return out

    ui_l = route((ui % rows).astype(np.int32))
    vj_s = route(vj.astype(np.int32))
    r_s = route(r.astype(np.float32))
    conf_s = route(conf.astype(np.float32))
    valid = (np.arange(Bs)[None, None, :] < counts[:, :, None]).astype(np.float32)
    rid = route(np.arange(nb * B, dtype=np.int32).reshape(nb, B))
    routed_extras = tuple(
        route(np.asarray(x, np.float32)) for x in extras)
    return (ui_l, vj_s, r_s, conf_s, valid, rid) + routed_extras


# ---------------------------------------------------------------------------
# The SPMD step: local Eq. 9-11 + all_to_all gradient-message exchange.
# ---------------------------------------------------------------------------
def build_outbox(gp, tbl_idx, tbl_wgt, vj):
    """Fixed-shape per-destination outbox for one minibatch on one shard.

    Pure function of the P-gradient messages ``gp (B, K)``, the *static*
    destination-partitioned graph tables ``tbl_idx/tbl_wgt (B, D, S)``
    (gathered for the batch's senders), and the batch item ids ``vj (B,)``.
    It has no access to ratings, confidences, u, or q — the privacy
    invariant "only global-factor gradients leave a learner" is structural
    here, and tests/test_dmf_sharded.py asserts the content is a function
    of gp alone (given the static tables): equal errors => equal outbox,
    whatever the ratings were.

    Returns (weights (D, B, S), local receiver rows (D, B, S),
    gradients (D, B, K), item ids (D, B)) — destination-major, ready for
    one `all_to_all` per tensor.
    """
    D = tbl_idx.shape[1]
    out_w = jnp.transpose(tbl_wgt, (1, 0, 2))
    out_i = jnp.transpose(tbl_idx, (1, 0, 2))
    out_g = jnp.broadcast_to(gp[None], (D,) + gp.shape)
    out_v = jnp.broadcast_to(vj[None], (D,) + vj.shape)
    return out_w, out_i, out_g, out_v


def _sharded_batch_update(U, P, Q, pidx, pwgt, ui, vj, r, conf, valid, noise,
                          cfg: dmf_lib.DMFConfig, prop_now=None,
                          online_local=None, byz=None, amul=None, ashill=None,
                          dirs=None, vjm=None, bkt=None, byz_cap=0,
                          tele=False):
    """One minibatch of Alg. 1 on one shard: local gathers + Eq. 9-11 via
    the SAME `dmf._step_deltas` as the single-device paths (the equivalence
    suite leans on that), local U/Q scatters, and the cross-shard P-gradient
    exchange.

    Noise-before-routing (DESIGN.md §9): with DP on, the clip+noise
    mechanism runs on ``gp`` HERE — before `build_outbox` and the
    `all_to_all` — so what crosses the shard boundary is already the
    noised message; no shard ever holds a peer's raw gradient. ``noise``
    is the batch rows' pre-scaled σC block, gathered from the epoch's
    counter-stream draw by each row's GLOBAL stream id — bit-identical to
    what the single-device scan adds, whatever shard the row landed on.
    The PR 3 privacy invariant (outbox = pure function of the message +
    static tables) is preserved with ``gp`` simply replaced by its DP
    release.

    Fault gates (robustness/faults.py; both None on the fault-free path):
    ``prop_now`` (B,) restricts a straggler row's scatter to the sender's
    own self slot (dest shard == me AND local row == sender), pre-outbox —
    its neighbor deliveries come from the delay ring later; ``online_local``
    (rows,) zeroes received weights into this shard's offline rows.
    Returns the released message block ``gp`` too (the churn epoch buffers
    it); the fault-free epoch discards it.

    Byzantine path (``byz`` a static `DefenseConfig`; None = untouched
    trace, see `dmf._sparse_batch_update_messages`): the sender's line-11
    self update stays honest and pre-outbox; outgoing messages are
    corrupted per the routed attack arrays BEFORE `build_outbox` (what
    crosses the wire is the corrupted release — the outbox purity
    invariant holds with gp replaced by the adversary's choice), screened
    on the RECEIVING shard after the `all_to_all` (each shard defends
    itself), and robust-combined per (receiver, item) bucket when
    ``byz.aggregation != "sum"`` (``bkt`` the host-compiled per-shard
    `MessageGroups` arrays in received-slot order).

    Telemetry (``tele``, static; obs/telemetry.py): when True a sixth
    return value carries this shard's TELE_W read-only reductions —
    message counts are RECEIVED deliveries (post fault gates), so each
    shard's slot 4 is "messages routed to me" and the shard sum matches
    the single-device delivery count. False (the default) traces none of
    it — the compiled program is unchanged."""
    theta = cfg.lr
    if cfg.dp and cfg.mode != "ldmf":
        du, gp, dq, loss = dmf_lib._step_deltas_dp(
            U, P, Q, ui, vj, r, conf, cfg, valid, noise)
    else:
        du, gp, dq, loss = dmf_lib._step_deltas(
            U, P, Q, ui, vj, r, conf, cfg, valid)
    U = U.at[ui].add(du)
    if cfg.mode != "gdmf":
        Q = Q.at[ui, vj].add(dq)
    if tele:
        z = jnp.zeros((), du.dtype)
        u_sq = jnp.sum(du * du)
        q_sq = jnp.sum(dq * dq) if cfg.mode != "gdmf" else z
    if cfg.mode == "ldmf":
        if tele:   # purely local: nothing released, nothing scattered
            return U, P, Q, loss, gp, jnp.stack(
                [u_sq, q_sq, z, z, z, z, z])
        return U, P, Q, loss, gp
    if byz is None:
        # lines 11 + 13-15 across shards: gather the batch senders' rows of
        # the destination-partitioned table, exchange, scatter locally.
        pi, pw = pidx[ui], pwgt[ui]                  # (B, D, S)
        if prop_now is not None:
            me = jax.lax.axis_index(AXIS)
            D = pi.shape[1]
            selfm = ((jnp.arange(D)[None, :, None] == me)
                     & (pi == ui[:, None, None])).astype(pw.dtype)
            pw = pw * jnp.maximum(prop_now[:, None, None], selfm)
        out_w, out_i, out_g, out_v = build_outbox(gp, pi, pw, vj)
        rw = jax.lax.all_to_all(out_w, AXIS, 0, 0)   # (D, B, S) source-major
        ri = jax.lax.all_to_all(out_i, AXIS, 0, 0)
        rg = jax.lax.all_to_all(out_g, AXIS, 0, 0)   # (D, B, K)
        rv = jax.lax.all_to_all(out_v, AXIS, 0, 0)   # (D, B)
        if online_local is not None:
            rw = rw * online_local[ri]               # offline receivers get 0
        upd = rw[..., None] * rg[:, :, None, :]      # (D, B, S, K)
        P = P.at[ri, rv[:, :, None]].add(-theta * upd)
        if tele:
            me = jax.lax.axis_index(AXIS)
            D = rw.shape[0]
            # received self slots (source shard == me, receiver == sender)
            # don't count as routed messages — matches the single-device
            # neighbor-delivery count when summed over shards
            selfr = ((jnp.arange(D)[:, None, None] == me)
                     & (ri == ui[None, :, None])).astype(rw.dtype)
            n_msgs = jnp.sum((rw * (1.0 - selfr) > 0).astype(rw.dtype))
            gp2r = jnp.sum(rg * rg, axis=-1)         # (D, B)
            scatter_sq = theta * theta * jnp.sum(
                gp2r * jnp.sum(rw * rw, axis=-1))
            return U, P, Q, loss, gp, jnp.stack(
                [u_sq, q_sq, jnp.sum(gp * gp), scatter_sq, n_msgs, z, z])
        return U, P, Q, loss, gp
    from repro.robustness import byzantine as byz_lib
    K = gp.shape[-1]
    pi, pw = pidx[ui], pwgt[ui]                      # (B, D, S)
    me = jax.lax.axis_index(AXIS)
    D = pi.shape[1]
    selfm = ((jnp.arange(D)[None, :, None] == me)
             & (pi == ui[:, None, None])).astype(pw.dtype)
    w_self = jnp.sum(pw * selfm, axis=(1, 2))
    if online_local is not None:
        w_self = w_self * online_local[ui]
    P = P.at[ui, vj].add(-theta * w_self[:, None] * gp)
    pw_msg = pw * (1.0 - selfm)
    if prop_now is not None:
        pw_msg = pw_msg * prop_now[:, None, None]
    gp_sent = gp
    if amul is not None:
        gp_sent = byz_lib.corrupt_messages(gp, amul, ashill, dirs[ui])
    vj_out = vjm if vjm is not None else vj
    out_w, out_i, out_g, out_v = build_outbox(gp_sent, pi, pw_msg, vj_out)
    rw = jax.lax.all_to_all(out_w, AXIS, 0, 0)       # (D, B, S) source-major
    ri = jax.lax.all_to_all(out_i, AXIS, 0, 0)
    rg = jax.lax.all_to_all(out_g, AXIS, 0, 0)       # (D, B, K)
    rv = jax.lax.all_to_all(out_v, AXIS, 0, 0)       # (D, B)
    if online_local is not None:
        rw = rw * online_local[ri]
    rw_pre = rw   # pre-screen delivery weights (telemetry baseline)
    if byz.screen:
        ok = byz_lib.screen_ok(rg, byz.norm_cap)     # (D, B)
        rg = jnp.where(ok[..., None] > 0, rg, 0.0)
        rw = rw * ok[:, :, None]
    # 0·NaN = NaN: zero-weight slots must deliver exactly 0 even when the
    # (undefended) message content is a bomb. With screening on, rg is
    # already zeroed wherever it was non-finite, so the plain multiply is
    # safe — and ±0 contributions leave the scatter-add bitwise unchanged.
    if byz.screen:
        upd = rw[..., None] * rg[:, :, None, :]
    else:
        upd = jnp.where((rw > 0)[..., None],
                        rw[..., None] * rg[:, :, None, :], 0.0)
    if byz.aggregation == "sum":
        P = P.at[ri, rv[:, :, None]].add(-theta * upd)
        scat = upd
    else:
        b_id, b_pos, b_recv, b_item = bkt
        vals = upd.reshape(-1, K)                    # (D·B·S, K) recv order
        validity = (rw > 0).astype(gp.dtype).reshape(-1)
        comb = byz_lib.robust_combine(
            vals, validity, b_id.reshape(-1), b_pos.reshape(-1),
            b_recv.shape[-1], byz_cap, byz)
        P = P.at[b_recv, b_item].add(-theta * comb)
        scat = comb
    if tele:
        n_pre = jnp.sum((rw_pre > 0).astype(pw.dtype))   # attempted
        n_post = jnp.sum((rw > 0).astype(pw.dtype))      # survived screen
        self_sq = jnp.sum((w_self[:, None] * gp) ** 2)
        scatter_sq = theta * theta * (self_sq + jnp.sum(scat * scat))
        return U, P, Q, loss, gp_sent, jnp.stack(
            [u_sq, q_sq, jnp.sum(gp_sent * gp_sent), scatter_sq,
             n_pre, n_post, n_pre - n_post])
    return U, P, Q, loss, gp_sent


@functools.partial(
    jax.jit, static_argnames=("cfg", "mesh", "tele"), donate_argnums=(0, 1, 2))
def _epoch_sharded(U, P, Q, pidx, pwgt, ui, vj, r, conf, valid, rid, dp_seed,
                   cfg, mesh, tele: bool = False):
    """A full epoch as ONE SPMD dispatch: shard_map over the learner axis,
    `lax.scan` over minibatches inside. Inputs: U (I_pad, K), P/Q
    (I_pad, J, K), tables (I_pad, D, S), batches (nb, D, Bs), plus the
    routed global stream ids ``rid`` (nb, D, Bs) and the per-epoch traced
    ``dp_seed`` keying the DP noise (dead inputs when DP is off). With DP
    noise on, every shard draws the SAME full-epoch noise block from the
    counter stream (one vectorized pass, replicated compute — noise is
    (n, K), small next to P) and gathers its routed rows' slices by rid:
    bit-identical noise to the single-device scan for every row, any mesh
    width. Returns the updated factors and per-(batch, shard) losses
    (nb, D)."""
    from repro.privacy import mechanism
    noise_on = cfg.dp and cfg.mode != "ldmf" and mechanism.noise_std(cfg) > 0

    def shard_body(U, P, Q, pidx, pwgt, ui, vj, r, conf, valid, rid, dp_seed):
        ui, vj, r, conf, valid, rid = (
            x[:, 0] for x in (ui, vj, r, conf, valid, rid))
        if noise_on:
            from repro.kernels.dp_noise import gauss_counter
            nb = ui.shape[0]
            K = U.shape[-1]
            all_rid = jnp.arange(
                nb * cfg.batch_size, dtype=jnp.int32).reshape(-1, 1)
            Z = mechanism.noise_std(cfg) * gauss_counter(dp_seed, all_rid, K)

        def body(carry, batch):
            U, P, Q = carry
            b_ui, b_vj, b_r, b_conf, b_val, b_rid = batch
            out = _sharded_batch_update(
                U, P, Q, pidx, pwgt, b_ui, b_vj, b_r, b_conf, b_val,
                Z[b_rid] if noise_on else None, cfg, tele=tele)
            if tele:
                U, P, Q, loss, _, tvec = out
                return (U, P, Q), (loss, tvec)
            U, P, Q, loss, _ = out
            return (U, P, Q), loss

        (U, P, Q), ys = jax.lax.scan(
            body, (U, P, Q), (ui, vj, r, conf, valid, rid))
        if tele:
            losses, tvecs = ys
            # (1, TELE_W) per shard -> (D, TELE_W) at the out spec
            return U, P, Q, losses[:, None], tvecs.sum(axis=0)[None]
        return U, P, Q, ys[:, None]

    out_specs = (P_(AXIS), P_(AXIS), P_(AXIS), P_(None, AXIS))
    if tele:
        out_specs += (P_(AXIS),)
    return jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P_(AXIS), P_(AXIS), P_(AXIS), P_(AXIS), P_(AXIS),
                  P_(None, AXIS), P_(None, AXIS), P_(None, AXIS),
                  P_(None, AXIS), P_(None, AXIS), P_(None, AXIS), P_()),
        out_specs=out_specs,
        check_vma=False,
    )(U, P, Q, pidx, pwgt, ui, vj, r, conf, valid, rid, dp_seed)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "use_ring", "byz", "use_attack",
                     "byz_cap", "tele"),
    donate_argnums=(0, 1, 2))
def _epoch_sharded_churn(U, P, Q, pidx, pwgt, dpidx, dpwgt, ui, vj, r, conf,
                         valid, rid, prop_now, online, ring_gp, ring_ui,
                         ring_vj, ring_deliver, dp_seed, amul, ashill, vjm,
                         dirs, b_id, b_pos, b_recv, b_item, cfg, mesh,
                         use_ring, byz=None, use_attack=False, byz_cap=0,
                         tele: bool = False):
    """`_epoch_sharded` under a fault schedule — STILL one SPMD dispatch.

    Extra inputs: the fault gates (``prop_now`` routed like the batches,
    ``online`` (I_pad,) row-sharded), the SAME partitioned table a second
    time sharded by DESTINATION (``dpidx``/``dpwgt`` with spec
    P(None, learners) → each shard holds every sender's receiver-list
    destined for ITS rows — what stale-message delivery needs, no comms),
    and the replicated delay-ring content. Start-of-epoch delivery scatters
    each due buffered message into the local P rows (neighbor slots only,
    receiver-online gated). The epoch's released messages are re-assembled
    into a replicated (n, K) stream block for the ring: each shard scatters
    its routed rows' gp by global stream id, then one `psum` (padded rows
    carry gp=0/rid=0 — they add zero). Returns (U, P, Q, losses, block).

    Under the trivial schedule (gates all ones, ``use_ring=False``) every
    fault op multiplies by 1.0 — the outputs are bitwise `_epoch_sharded`'s.

    Byzantine args (``byz``/``use_attack``/``byz_cap`` static; attack
    arrays routed like the batches, ``dirs`` row-sharded, bucket arrays in
    per-destination received-slot order with spec P(None, learners)):
    with ``byz=None`` every one is a statically dead input and the trace
    is unchanged. Ring messages are screened AT DELIVERY on the receiving
    shard — stale corrupted messages don't dodge the gate."""
    from repro.privacy import mechanism
    noise_on = cfg.dp and cfg.mode != "ldmf" and mechanism.noise_std(cfg) > 0
    theta = cfg.lr
    robust = byz is not None and byz.aggregation != "sum"

    def shard_body(U, P, Q, pidx, pwgt, dpidx, dpwgt, ui, vj, r, conf, valid,
                   rid, prop_now, online, ring_gp, ring_ui, ring_vj,
                   ring_deliver, dp_seed, amul, ashill, vjm, dirs, b_id,
                   b_pos, b_recv, b_item):
        ui, vj, r, conf, valid, rid, prop_now = (
            x[:, 0] for x in (ui, vj, r, conf, valid, rid, prop_now))
        rows = U.shape[0]
        K = U.shape[-1]
        me = jax.lax.axis_index(AXIS)
        if use_ring:
            # deliver the buffered messages due THIS epoch into local P rows
            gflat = ring_gp.reshape(-1, K)               # (L·n, K)
            di = dpidx[ring_ui, 0]                       # (L·n, S) local rows
            dw = dpwgt[ring_ui, 0]
            selfm = ((me * rows + di) == ring_ui[:, None]).astype(dw.dtype)
            dw = (dw * (1.0 - selfm) * online[di]
                  * ring_deliver[:, None])
            if byz is not None:
                from repro.robustness import byzantine as byz_lib
                if byz.screen:
                    okd = byz_lib.screen_ok(gflat, byz.norm_cap)
                    gflat = jnp.where(okd[:, None] > 0, gflat, 0.0)
                    dw = dw * okd[:, None]
                dupd = jnp.where((dw > 0)[:, :, None],
                                 dw[:, :, None] * gflat[:, None, :], 0.0)
            else:
                dupd = dw[:, :, None] * gflat[:, None, :]
            P = P.at[di, ring_vj[:, None]].add(-theta * dupd)
        if noise_on:
            from repro.kernels.dp_noise import gauss_counter
            nb = ui.shape[0]
            all_rid = jnp.arange(
                nb * cfg.batch_size, dtype=jnp.int32).reshape(-1, 1)
            Z = mechanism.noise_std(cfg) * gauss_counter(dp_seed, all_rid, K)

        xs = [ui, vj, r, conf, valid, rid, prop_now]
        if use_attack:
            xs += [amul[:, 0], ashill[:, 0]]
        if byz is not None:
            xs.append(vjm[:, 0])
        if robust:
            xs += [b_id[:, 0], b_pos[:, 0], b_recv[:, 0], b_item[:, 0]]

        def body(carry, batch):
            U, P, Q = carry
            b_ui, b_vj, b_r, b_conf, b_val, b_rid, b_prop = batch[:7]
            i = 7
            b_amul = b_ashill = b_vjm = bkt = None
            if use_attack:
                b_amul, b_ashill = batch[i], batch[i + 1]
                i += 2
            if byz is not None:
                b_vjm = batch[i]
                i += 1
            if robust:
                bkt = batch[i:i + 4]
            out = _sharded_batch_update(
                U, P, Q, pidx, pwgt, b_ui, b_vj, b_r, b_conf, b_val,
                Z[b_rid] if noise_on else None, cfg,
                prop_now=b_prop, online_local=online, byz=byz,
                amul=b_amul, ashill=b_ashill,
                dirs=dirs if use_attack else None, vjm=b_vjm, bkt=bkt,
                byz_cap=byz_cap, tele=tele)
            if tele:
                U, P, Q, loss, gp, tvec = out
            else:
                U, P, Q, loss, gp = out
            y = [loss]
            if use_ring:
                y.append(gp)
            if tele:
                y.append(tvec)
            return (U, P, Q), (tuple(y) if len(y) > 1 else y[0])

        (U, P, Q), ys = jax.lax.scan(body, (U, P, Q), tuple(xs))
        tvecs = None
        if tele:
            ys, tvecs = (ys[:-1], ys[-1])
            ys = ys if use_ring else ys[0]
        if use_ring:
            losses, gps = ys
            # replicated released-message stream block for the delay ring:
            # scatter-add my rows by global stream id, psum across shards
            n_stream = ui.shape[0] * cfg.batch_size
            blk = jnp.zeros((n_stream, K), gps.dtype)
            blk = blk.at[rid.reshape(-1)].add(gps.reshape(-1, K))
            blk = jax.lax.psum(blk, AXIS)
        else:
            losses = ys
            blk = jnp.zeros((1, K), jnp.float32)
        ret = (U, P, Q, losses[:, None], blk)
        if tele:
            # (1, TELE_W) per shard -> (D, TELE_W) at the out spec
            ret += (tvecs.sum(axis=0)[None],)
        return ret

    out_specs = (P_(AXIS), P_(AXIS), P_(AXIS), P_(None, AXIS), P_())
    if tele:
        out_specs += (P_(AXIS),)
    return jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P_(AXIS), P_(AXIS), P_(AXIS), P_(AXIS), P_(AXIS),
                  P_(None, AXIS), P_(None, AXIS),
                  P_(None, AXIS), P_(None, AXIS), P_(None, AXIS),
                  P_(None, AXIS), P_(None, AXIS), P_(None, AXIS),
                  P_(None, AXIS), P_(AXIS),
                  P_(), P_(), P_(), P_(), P_(),
                  P_(None, AXIS), P_(None, AXIS), P_(None, AXIS), P_(AXIS),
                  P_(None, AXIS), P_(None, AXIS), P_(None, AXIS),
                  P_(None, AXIS)),
        out_specs=out_specs,
        check_vma=False,
    )(U, P, Q, pidx, pwgt, dpidx, dpwgt, ui, vj, r, conf, valid, rid,
      prop_now, online, ring_gp, ring_ui, ring_vj, ring_deliver, dp_seed,
      amul, ashill, vjm, dirs, b_id, b_pos, b_recv, b_item)


def train_epoch_churn_sharded(
    state: dmf_lib.DMFState,
    prop,
    train: np.ndarray,
    cfg: dmf_lib.DMFConfig,
    rng: np.random.Generator,
    t: int,
    schedule,                   # robustness.faults.ChurnPlan
    ring,                       # robustness.faults.DelayRing | None
    accountant=None,
    attack=None,                # robustness.byzantine.AttackPlan | None
    byz=None,                   # robustness.byzantine.DefenseConfig | None
    tele: bool = False,         # append the (n_shards, TELE_W) device stats
) -> tuple[dmf_lib.DMFState, float]:
    """Sharded counterpart of `dmf.train_epoch_churn`: the same sampled
    stream and fault gates (host-side, shard-count-independent), rows and
    gates routed to home shards, one SPMD dispatch per epoch. The delay
    ring is replicated — its written content is the psum-assembled global
    released-message stream, so a run's ring state is invariant to the
    mesh width (and a resume can switch shard counts).

    ``attack``/``byz`` mirror the single-device path: the attack arrays
    are realized on the ROUTED stream (same per-(user, epoch) corruption,
    whatever shard a row landed on), message-bucket membership is compiled
    per destination shard in received-slot order, and screening decisions
    depend only on message content + τ — all shard-count invariant
    (tests/test_byzantine.py pins defended runs across mesh widths)."""
    plan = _as_plan(prop, cfg)
    ui, vj, r, conf = dmf_lib.sample_epoch(train, cfg, rng)
    B = cfg.batch_size
    nb = len(ui) // B
    n = nb * B
    shape = (nb, B)
    ui2 = ui[:n].reshape(shape)
    vj2 = vj[:n].reshape(shape)
    _, dp_seed = dmf_lib.epoch_dp_inputs(cfg, rng, n)
    on, sender_on, prop_now, due = schedule.epoch_row_masks(t, ui2)
    conf2 = conf[:n].reshape(shape) * sender_on
    if accountant is not None:
        accountant.observe_epoch(ui2, valid=sender_on)
    ui_l, vj_s, r_s, conf_s, valid, rid, son_s, pnow_s = shard_batches(
        ui2, vj2, r[:n].reshape(shape), conf2, cfg.n_shards, plan.rows,
        extras=(sender_on, prop_now))
    valid = valid * son_s       # offline senders' routed rows are inert
    online_pad = np.zeros(plan.n_rows_padded, np.float32)
    online_pad[: schedule.n_users] = on
    use_ring = ring is not None
    if use_ring:
        r_ui = ring.ui.reshape(-1)
        r_vj = ring.vj.reshape(-1)
        r_del = (ring.due.reshape(-1) == t).astype(np.float32)
        ring_gp = ring.gp
    else:  # statically-skipped dummies (dead jit inputs)
        r_ui = np.zeros(1, np.int32)
        r_vj = np.zeros(1, np.int32)
        r_del = np.zeros(1, np.float32)
        ring_gp = jnp.zeros((1, 1, cfg.dim), jnp.float32)
    use_attack = attack is not None
    K = cfg.dim
    if use_attack:
        assert byz is not None
        # realize the attack on the routed stream by GLOBAL user id —
        # identical per-(user, epoch) corruption at every mesh width;
        # padded slots are forced honest via the routed validity
        gl_ui = (np.arange(cfg.n_shards)[None, :, None] * plan.rows
                 + ui_l).astype(np.int64)
        amul, ashill, vjm = attack.epoch_row_attack(
            t, gl_ui, vj_s, sender_on=(valid > 0))
        # the ring buffers the UNSHARDED stream: same realization there
        amul_g, ashill_g, vjm_g = attack.epoch_row_attack(
            t, ui2, vj2, sender_on=sender_on)
        dirs_pad = np.zeros((plan.n_rows_padded, K), np.float32)
        dirs_pad[: schedule.n_users] = attack.dirs
        dirs = jnp.asarray(dirs_pad)
    else:
        amul = ashill = np.zeros((1, cfg.n_shards, 1), np.float32)
        vjm = vj_s
        vjm_g = vj2
        dirs = jnp.zeros((cfg.n_shards, K), jnp.float32)
    robust = byz is not None and byz.aggregation != "sum"
    if robust:
        from repro.robustness import byzantine as byz_lib
        groups = byz_lib.group_messages_sharded(
            ui_l, vjm, valid, plan.part.idx, plan.part.wgt, plan.rows,
            cfg.n_shards, cfg.n_items, prop_now=pnow_s, online=online_pad)
        gb = (jnp.asarray(groups.bucket_id), jnp.asarray(groups.pos),
              jnp.asarray(groups.recv), jnp.asarray(groups.item))
        byz_cap = groups.cap
    else:
        z3 = np.zeros((1, cfg.n_shards, 1), np.int32)
        gb = (z3, z3, z3, z3)
        byz_cap = 0
    st = shard_state(state, plan)
    out = _epoch_sharded_churn(
        st.U, st.P, st.Q, plan.part.idx, plan.part.wgt,
        plan.part.idx, plan.part.wgt,
        jnp.asarray(ui_l), jnp.asarray(vj_s), jnp.asarray(r_s),
        jnp.asarray(conf_s), jnp.asarray(valid), jnp.asarray(rid),
        jnp.asarray(pnow_s), jnp.asarray(online_pad),
        ring_gp, jnp.asarray(r_ui), jnp.asarray(r_vj), jnp.asarray(r_del),
        jnp.asarray(dp_seed, jnp.int32),
        jnp.asarray(amul), jnp.asarray(ashill), jnp.asarray(vjm), dirs,
        gb[0], gb[1], gb[2], gb[3],
        cfg, plan.mesh, use_ring, byz, use_attack, byz_cap, tele=tele)
    U, Pm, Q, losses, blk = out[:5]
    if use_ring:
        ring.write(t, blk, ui2, vjm_g if byz is not None else vj2, due)
    total = float(np.asarray(losses, dtype=np.float64).sum())
    realized = int(sender_on.sum())
    l = total / max(realized, 1)
    if tele:
        return dmf_lib.DMFState(U, Pm, Q), l, np.asarray(out[5])
    return dmf_lib.DMFState(U, Pm, Q), l


def _as_plan(prop, cfg: dmf_lib.DMFConfig) -> ShardPlan:
    if isinstance(prop, ShardPlan):
        assert prop.n_shards == cfg.n_shards, (prop.n_shards, cfg.n_shards)
        return prop
    if not isinstance(prop, graph_lib.NeighborTable):
        prop = graph_lib.neighbor_table_from_dense(np.asarray(prop))
    return make_shard_plan(prop, cfg)


def shard_state(state: dmf_lib.DMFState, plan: ShardPlan) -> dmf_lib.DMFState:
    """Pad the learner axis to the mesh and place each factor with its
    row sharding (no-op if already padded; re-placement is cheap then)."""
    sh = NamedSharding(plan.mesh, P(AXIS))
    st = pad_state(state, plan.n_rows_padded)
    return dmf_lib.DMFState(
        U=jax.device_put(st.U, sh),
        P=jax.device_put(st.P, sh),
        Q=jax.device_put(st.Q, sh),
    )


def train_epoch_sharded(
    state: dmf_lib.DMFState,
    prop,                       # ShardPlan | graph.NeighborTable | dense M
    train: np.ndarray,
    cfg: dmf_lib.DMFConfig,
    rng: np.random.Generator,
    accountant=None,
    tele: bool = False,         # append the (n_shards, TELE_W) device stats
) -> tuple[dmf_lib.DMFState, float]:
    """Sharded counterpart of `dmf.train_epoch`: identical minibatch stream
    (same rng consumption — the per-epoch DP seed draw included, so DP-on
    noise matches the single-device epoch bit-for-bit), rows routed to home
    shards, one SPMD dispatch. Returns a state whose learner axis stays
    padded+sharded across epochs (donated buffers, no per-epoch host
    round-trip); slice with `unpad_state` when done — `dmf.fit` does both
    automatically. ``accountant`` observes the realized stream like the
    single-device path (ε accounting is shard-count-independent)."""
    plan = _as_plan(prop, cfg)
    ui, vj, r, conf = dmf_lib.sample_epoch(train, cfg, rng)
    B = cfg.batch_size
    nb = len(ui) // B
    n = nb * B
    shape = (nb, B)
    _, dp_seed = dmf_lib.epoch_dp_inputs(cfg, rng, n)
    if accountant is not None:
        accountant.observe_epoch(ui[:n].reshape(shape))
    ui_l, vj_s, r_s, conf_s, valid, rid = shard_batches(
        ui[:n].reshape(shape), vj[:n].reshape(shape),
        r[:n].reshape(shape), conf[:n].reshape(shape),
        cfg.n_shards, plan.rows)
    st = shard_state(state, plan)
    out = _epoch_sharded(
        st.U, st.P, st.Q, plan.part.idx, plan.part.wgt,
        jnp.asarray(ui_l), jnp.asarray(vj_s), jnp.asarray(r_s),
        jnp.asarray(conf_s), jnp.asarray(valid), jnp.asarray(rid),
        jnp.asarray(dp_seed, jnp.int32), cfg, plan.mesh, tele=tele)
    U, Pm, Q, losses = out[:4]
    total = float(np.asarray(losses, dtype=np.float64).sum())
    l = total / max(n, 1)
    if tele:
        return dmf_lib.DMFState(U, Pm, Q), l, np.asarray(out[4])
    return dmf_lib.DMFState(U, Pm, Q), l


# ---------------------------------------------------------------------------
# Sharded evaluation: per-user top-k is row-parallel — no communication.
# ---------------------------------------------------------------------------
def evaluate_sharded(
    state: dmf_lib.DMFState, train: np.ndarray, test: np.ndarray,
    n_users: int, n_items: int, n_shards: int, ks=(5, 10),
    chunk_users: int | None = None,
) -> dict[str, float]:
    """`dmf.evaluate` over the learner mesh: each shard streams its own
    users' (rows, J, K) factors through the per-user top-k kernel; results
    concatenate along the learner axis. Bit-identical to the single-device
    kernel per user (row-parallel, no cross-shard reads).

    ``chunk_users`` bounds the per-shard rows staged per dispatch: the
    evaluation walks local row windows of that width across all shards at
    once, building each window's V = P + Q view and train/test mask rows on
    the fly — the full (I, J, K) V and (I, J) masks never co-materialize
    with the factors. Results are identical to the unchunked path (per-user
    hit counts are integers, reduced once at the end)."""
    from repro.kernels import ops

    mesh = make_learner_mesh(n_shards)
    rows = rows_per_shard(n_users, n_shards)
    I_pad = rows * n_shards
    kmax = max(ks)
    st = unpad_state(state, n_users)

    def body(U_loc, V_loc, m_loc):
        return ops.recommend_topk_peruser(U_loc, V_loc, m_loc, kmax)

    dispatch = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
        check_vma=False,
    ))

    if chunk_users is None:
        train_mask = metrics_lib.masks_from_interactions(
            n_users, n_items, train)
        test_mask = metrics_lib.masks_from_interactions(n_users, n_items, test)
        U = pad_rows(st.U, I_pad)
        V = pad_rows(st.P + st.Q, I_pad)
        mask = pad_rows(jnp.asarray(train_mask.astype(np.int8)), I_pad)
        _, idx = dispatch(U, V, mask)
        return metrics_lib.evaluate_ranking_from_topk(
            np.asarray(idx)[:n_users], test_mask, ks)

    rc = min(max(int(chunk_users), 1), rows)
    hits: dict[int, list[np.ndarray]] = {k: [] for k in ks}
    n_test_parts: list[np.ndarray] = []
    order_parts: list[np.ndarray] = []
    for t in range(0, rows, rc):
        width = min(rc, rows - t)
        U_parts, V_parts, m_parts, ts_parts, gids = [], [], [], [], []
        for d in range(n_shards):
            g0 = d * rows + t
            ids = np.arange(g0, g0 + width)
            safe = jnp.asarray(np.minimum(ids, max(n_users - 1, 0)))
            U_parts.append(st.U[safe])
            V_parts.append(st.P[safe] + st.Q[safe])
            m_parts.append(metrics_lib.masks_from_interactions_rows(
                g0, width, n_items, train))
            ts_parts.append(metrics_lib.masks_from_interactions_rows(
                g0, width, n_items, test))
            gids.append(ids)
        _, idx = dispatch(
            jnp.concatenate(U_parts), jnp.concatenate(V_parts),
            jnp.asarray(np.concatenate(m_parts).astype(np.int8)))
        rec = np.asarray(idx)
        ts = np.concatenate(ts_parts)
        ids = np.concatenate(gids)
        real = ids < n_users
        for k in ks:
            hits[k].append(metrics_lib.topk_hits(rec, ts, k)[real])
        n_test_parts.append(ts.sum(axis=1)[real])
        order_parts.append(ids[real])
    # windows interleave shards — restore global user order so the float
    # reduction matches the unchunked mean exactly
    order = np.argsort(np.concatenate(order_parts), kind="stable")
    n_test = np.concatenate(n_test_parts)[order]
    out = {}
    for k in ks:
        p, r = metrics_lib.precision_recall_from_hits(
            np.concatenate(hits[k])[order], n_test, k)
        out[f"P@{k}"] = p
        out[f"R@{k}"] = r
    return out
