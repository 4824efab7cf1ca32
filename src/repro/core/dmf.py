"""Decentralized Matrix Factorization — the paper's Algorithm 1 in JAX.

Model (paper Eqs. 5-8): each user i ("learner") privately holds
  * u_i            — user latent factor                  (K,)
  * p^i = P[i]     — his copy of the *common* item factors (J, K)
  * q^i = Q[i]     — his *personal* item factors           (J, K)
with the effective item factor v^i_j = p^i_j + q^i_j.

Objective (Eq. 6) with least-square loss (Eq. 7) and gradients (Eqs. 9-11):
  ∂L/∂u_i  = -(r - u·v) v + α u
  ∂L/∂p^i_j = -(r - u·v) u + β p^i_j
  ∂L/∂q^i_j = -(r - u·v) u + γ q^i_j

Per Alg. 1, when user i rates item j he updates (u_i, p^i_j, q^i_j) with SGD
and *sends the gradient of the global factor* ∂L/∂p^i_j to his d≤D-hop
neighbors, who apply it with random-walk weights — only gradients ever leave
a learner (the privacy mechanism). We vectorize this exactly: the
propagation matrix M (core/graph.py) carries M[i,i'] per (sender, receiver),
with M[i,i]=1 for the sender's own line-11 update, so one scatter

    P[:, j] -= θ · M[i, :]^T ⊗ ∂L/∂p^i_j

reproduces lines 11+15 for every receiver at once. The simulation is
faithful to the paper's own evaluation ("we mock decentralized learning").

Decentralized-semantics note: SGD is applied per *minibatch* (order-free sum
of per-rating contributions) rather than per single rating — required for
SPMD, standard minibatching of Alg. 1; the paper's per-rating updates are
recovered with batch_size=1.

Negative sampling (paper §Unobserved rating sample): for each observed
r_ij ∈ O we draw m unobserved (i, j') as r=0 with confidence 1/m; the
confidence scales the error term of the loss.

Modes (paper's ablations):
  * ``dmf``  — full model;
  * ``gdmf`` — γ→∞ limit: q^i ≡ 0, only the shared factor is learnt;
  * ``ldmf`` — β→∞ limit: p^i ≡ 0 and no exchange, purely local learning.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graph as graph_lib
from repro.core import metrics as metrics_lib
from repro.obs import trace as trace_lib


@dataclasses.dataclass(frozen=True)
class DMFConfig:
    n_users: int
    n_items: int
    dim: int = 10                    # K
    alpha: float = 0.1               # user regularizer (paper: 0.1)
    beta: float = 0.01               # global item regularizer
    gamma: float = 0.01              # personal item regularizer
    lr: float = 0.1                  # θ (paper: 0.1)
    neg_samples: int = 3             # m (paper: 3)
    batch_size: int = 256
    mode: str = "dmf"                # dmf | gdmf | ldmf
    init_scale: float = 0.1
    seed: int = 0
    use_pallas: bool = False         # fused Pallas step kernel (ops.dmf_fused_step)
    n_shards: int = 1                # learner-mesh width; >1 = SPMD epochs over
                                     # a row-sharded U/P/Q (sharding/dmf.py)
    dp_clip: float = float("inf")    # C — L2 bound per outgoing gradient message
    dp_sigma: float = 0.0            # σ — noise multiplier relative to C
    dp_seed: int = 0                 # DP mechanism base seed (privacy/mechanism.py)

    def __post_init__(self):
        assert self.mode in ("dmf", "gdmf", "ldmf"), self.mode
        assert self.n_shards >= 1, self.n_shards
        assert self.dp_sigma >= 0.0 and self.dp_clip > 0.0, (
            self.dp_sigma, self.dp_clip)
        import math
        assert self.dp_sigma == 0.0 or math.isfinite(self.dp_clip), (
            "dp_sigma > 0 needs a finite dp_clip: the noise std is σ·C")

    @property
    def dp(self) -> bool:
        """True iff outgoing gradient messages are clipped/noised
        (privacy/mechanism.py). False (the default σ=0, C=∞) compiles the
        exact un-noised program — bit-exact with the DP-less paths. Also
        False for ``ldmf``: purely-local learning exchanges nothing, so
        there is no mechanism to run, no rng seed draw, and no accountant
        — dp params are inert rather than producing an ε claim about
        releases that never happen."""
        if self.mode == "ldmf":
            return False
        from repro.privacy import mechanism
        return mechanism.dp_enabled(self)


@dataclasses.dataclass
class DMFState:
    U: jnp.ndarray   # (I, K)
    P: jnp.ndarray   # (I, J, K) per-learner copies of the common factor
    Q: jnp.ndarray   # (I, J, K) personal factors


# Registered as a pytree so the state checkpoints/restores as three leaves
# (checkpoint/ckpt.py flattens by key path) instead of one opaque object.
jax.tree_util.register_dataclass(
    DMFState, data_fields=["U", "P", "Q"], meta_fields=[])


def init_state(cfg: DMFConfig, rng: np.random.Generator | None = None) -> DMFState:
    """U random; P and Q zero.

    Zero item-factor init is the consensus-friendly choice for the
    decentralized setting: an item never touched by user i's D-hop
    neighborhood keeps score exactly u_i·0 = 0, i.e. neutral — with random
    init those items would carry O(|u||p0|) noise that pollutes top-k for
    every user (observed: random init halves P@5). U random breaks the
    u=v=0 saddle (p's first gradient is -e·u ≠ 0).
    """
    rng = rng or np.random.default_rng(cfg.seed)
    I, J, K = cfg.n_users, cfg.n_items, cfg.dim
    U = jnp.asarray(rng.normal(0, cfg.init_scale, (I, K)), dtype=jnp.float32)
    P = jnp.zeros((I, J, K), jnp.float32)
    Q = jnp.zeros((I, J, K), jnp.float32)
    return DMFState(U=U, P=P, Q=Q)


# ---------------------------------------------------------------------------
# One minibatch step of Algorithm 1 (lines 6-16), vectorized.
#
# Two implementations:
#   * `_batch_step` — dense reference (seed): propagates every gradient
#     through the full (I, I) walk matrix, O(I·B·K) per batch. Kept as the
#     equivalence oracle and for `fit(..., dense_reference=True)`.
#   * `_sparse_batch_update` — production path: gathers each sender's
#     compact neighbor row from a `graph.NeighborTable` and scatter-adds
#     into P, O(B·S·K) per batch (S = max 1+|N^D|; see DESIGN.md §5).
# ---------------------------------------------------------------------------
def _grads_and_loss(u, p, q, r, conf, cfg: DMFConfig):
    """Eqs. 9-11 gradients and batch loss for gathered (B, K) factors —
    the single definition shared by the dense and sparse step paths (the
    equivalence tests compare the two, so they must share this math)."""
    v = p + q
    err = conf * (r - jnp.sum(u * v, axis=-1))  # confidence-weighted residual
    gu = -err[:, None] * v + cfg.alpha * u
    gp = -err[:, None] * u + cfg.beta * p
    gq = -err[:, None] * u + cfg.gamma * q
    loss = 0.5 * jnp.sum(conf * (r - jnp.sum(u * v, -1)) ** 2)
    return gu, gp, gq, loss


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0, 1, 2))
def _batch_step(
    U: jnp.ndarray,
    P: jnp.ndarray,
    Q: jnp.ndarray,
    M: jnp.ndarray,            # (I, I) propagation matrix (incl. self)
    ui: jnp.ndarray,           # (B,) user indices
    vj: jnp.ndarray,           # (B,) item indices
    r: jnp.ndarray,            # (B,) ratings in [0,1]
    conf: jnp.ndarray,         # (B,) confidence weights (1 for pos, 1/m neg)
    cfg: DMFConfig,
):
    theta = cfg.lr
    u = U[ui]                                  # (B, K)
    p = P[ui, vj]                              # (B, K)
    q = Q[ui, vj]                              # (B, K)
    gu, gp, gq, loss = _grads_and_loss(u, p, q, r, conf, cfg)

    U = U.at[ui].add(-theta * gu)
    if cfg.mode != "gdmf":
        Q = Q.at[ui, vj].add(-theta * gq)
    if cfg.mode != "ldmf":
        # lines 11 + 13-15: sender's own update plus the random-walk
        # propagated gradient-exchange to all d<=D-hop neighbors.
        A = M[ui]                              # (B, I) receiver weights
        upd = A.T[:, :, None] * gp[None, :, :]  # (I, B, K)
        P = P.at[:, vj].add(-theta * upd)
    return U, P, Q, loss


def _step_deltas(U, P, Q, ui, vj, r, conf, cfg: DMFConfig, valid=None):
    """Gather + Eqs. 9-11 for one minibatch: returns the lr-scaled U/Q
    deltas ``(du, dq)``, the raw global-factor gradient message ``gp``
    (scaled by -θ and the walk weight at scatter time), and the batch loss.

    The SINGLE definition of the per-row step math, shared by every fast
    path — the sparse scan, the online refresh, and the learner-sharded
    SPMD epoch (sharding/dmf.py) — so they cannot silently diverge from
    each other or from the fused Pallas kernel behind ``cfg.use_pallas``.

    ``valid`` (optional (B,) bool/float) marks real rows in a padded batch.
    Invalid rows contribute exactly nothing: conf=0 already zeroes their
    error term, but the α/β/γ regularizer pulls survive in the gradients,
    so all three deltas are masked here, before any scatter."""
    theta = cfg.lr
    if cfg.use_pallas:
        from repro.kernels import ops
        du, gp, dq, loss = ops.dmf_fused_step(
            U[ui], P[ui, vj], Q[ui, vj], r, conf,
            theta=theta, alpha=cfg.alpha, beta=cfg.beta, gamma=cfg.gamma,
        )
    else:
        gu, gp, gq, loss = _grads_and_loss(U[ui], P[ui, vj], Q[ui, vj], r, conf, cfg)
        du = -theta * gu
        dq = -theta * gq
    if valid is not None:
        keep = valid.astype(du.dtype)[:, None]
        du = du * keep
        dq = dq * keep
        gp = gp * keep
    return du, gp, dq, loss


def _dp_noise_rows(rid, dp_seed, cfg: DMFConfig, k: int):
    """On-demand noise for a row set: the (len(rid), k) pre-scaled σC
    Gaussian block from the counter stream keyed by the rows' global
    stream ids — what the online refresh and the audit capture use per
    batch. The epoch scan instead generates the WHOLE epoch's block in one
    vectorized pass (see `_epoch_scan`) — same stream, same values, 70x
    fewer transcendental dispatches. Returns None when σ=0 (clip-only)."""
    from repro.kernels.dp_noise import gauss_counter
    from repro.privacy import mechanism
    std = mechanism.noise_std(cfg)
    if std == 0.0:
        return None
    return std * gauss_counter(
        dp_seed, jnp.asarray(rid, jnp.int32).reshape(-1, 1), k)


def _dp_message(gp, noise, cfg: DMFConfig, valid=None):
    """The DP mechanism's clip+noise over the outgoing message block — THE
    single place a P-gradient becomes an exchanged message on the jnp
    paths (the fused Pallas step applies the identical math in-kernel, and
    the sharded step runs this pre-`all_to_all`). ``noise`` is the rows'
    pre-scaled σC block (None = clip only); padded rows are re-masked
    because noise lands on their zero gradients too."""
    nrm = jnp.sqrt(jnp.sum(gp * gp, axis=-1, keepdims=True))
    gp = gp * jnp.minimum(1.0, cfg.dp_clip / nrm)   # inf/0 -> 1 (no-op)
    if noise is not None:
        gp = gp + noise
    if valid is not None:
        gp = gp * valid.astype(gp.dtype)[:, None]
    return gp


def _step_deltas_dp(U, P, Q, ui, vj, r, conf, cfg: DMFConfig, valid, noise):
    """`_step_deltas` with the DP mechanism on the outgoing gp message.

    On the Pallas path the clip + noise-add folds into the SAME fused step
    kernel (`ops.dmf_fused_step_dp`) — the DP epoch keeps the un-noised
    epoch's one-kernel-per-minibatch dispatch count. The jnp path applies
    `_dp_message` as a follow-on op (XLA fuses it into the step anyway)."""
    if cfg.use_pallas:
        from repro.kernels import ops
        z = noise if noise is not None else jnp.zeros_like(U[ui])
        du, gp, dq, loss = ops.dmf_fused_step_dp(
            U[ui], P[ui, vj], Q[ui, vj], r, conf, z,
            theta=cfg.lr, alpha=cfg.alpha, beta=cfg.beta, gamma=cfg.gamma,
            clip=cfg.dp_clip)
        if valid is not None:
            keep = valid.astype(du.dtype)[:, None]
            du, gp, dq = du * keep, gp * keep, dq * keep
        return du, gp, dq, loss
    du, gp, dq, loss = _step_deltas(U, P, Q, ui, vj, r, conf, cfg, valid)
    return du, _dp_message(gp, noise, cfg, valid), dq, loss


def _p_scatter_add(P, recv, items, upd):
    """``P[recv[b, s], items[b]] += upd[b, s]`` for every row b and slot s.

    ``recv`` (B, S) receivers, ``items`` (B,), ``upd`` (B, S, K). One
    scatter-add of the form ``P.at[(B,), (B,)].add((B, K))`` per receiver
    slot, unrolled over the static S. XLA compiles that form in place on P
    in its own layout. The single-scatter form ``P.at[recv, items[:, None]]``
    is linearized over I·J instead, which makes the compiler copy all of P
    into and out of a flattened buffer at every call. Repeated (receiver,
    item) pairs still sum; only the order of the float additions differs.
    """
    for s in range(recv.shape[1]):
        P = P.at[recv[:, s], items].add(upd[:, s])
    return P


def _sparse_batch_update_messages(U, P, Q, nbr_idx, nbr_wgt, ui, vj, r, conf,
                                  cfg: DMFConfig, valid=None, rid=None,
                                  dp_seed=None, noise=None, recv_gate=None,
                                  prop_now=None, byz=None, amul=None,
                                  ashill=None, dirs=None, vjm=None, bkt=None,
                                  byz_cap=0, tele=False):
    """One minibatch of Alg. 1 against the sparse neighbor table.

    Identical math to `_batch_step`; only the line 13-15 propagation differs:
    instead of weighting gp by a full (I,) column of M, each sender's (S,)
    receiver row is gathered and scatter-added — padded self-index slots
    carry weight 0 and are exact no-ops.

    With DP on (``cfg.dp``), the propagated message is clipped+noised
    before the scatter — every receiver, the sender's own line-11 P update
    included, applies only the noised message. Returns the per-row sent
    messages too (the observed outbox stream the audit harness attacks);
    `_sparse_batch_update` drops them for the training callers.

    Fault gates (robustness/faults.py; both None on the fault-free paths):
    ``recv_gate`` (I,) zeroes scatter weights into offline receivers —
    messages to an absent learner are lost, its P rows bit-frozen.
    ``prop_now`` (B,) restricts a straggler row's scatter to the sender's
    own line-11 self slot: its neighbor deliveries come from the delay
    ring k epochs later (`_epoch_scan_churn`). All-ones gates multiply
    weights by 1.0 — bit-exact with the ungated path.

    Byzantine path (robustness/byzantine.py; ``byz`` a `DefenseConfig`,
    static): with ``byz is None`` (the default) NONE of the code below the
    `byz is not None` branch is traced — the compiled program is the
    pre-existing one. Otherwise the exchange is restructured: the sender's
    own line-11 self update stays honest (an attacker poisons its *peers*,
    not its own copy — and the global loss metric must stay comparable),
    outgoing messages are corrupted per the attack arrays (``amul``/
    ``ashill``/``dirs``/``vjm``), screened at the receiver boundary
    (finite + norm-cap, content zeroed — 0·NaN is NaN), and combined per
    (receiver, item) bucket by trimmed-mean/median instead of plain
    summation when ``byz.aggregation != "sum"`` (``bkt`` the host-compiled
    `MessageGroups` arrays). Returns the SENT (post-corruption) messages —
    the delay ring must buffer what was actually released.

    Telemetry (``tele``, static; obs/telemetry.py): when True a sixth
    return value carries the ``TELE_W`` read-only reduction vector over
    intermediates this step already computes — squared update norms,
    released-message mass, scattered-propagation mass, delivery counts,
    screening accept/reject. No rng draw, no factor write, so factor
    trajectories are bit-identical with ``tele=False`` — and False (the
    default) traces none of it: the compiled program is unchanged.
    """
    theta = cfg.lr
    with jax.named_scope("dmf.gather_grads"):
        if cfg.dp and cfg.mode != "ldmf":
            if noise is None:
                noise = _dp_noise_rows(rid, dp_seed, cfg, U.shape[-1])
            du, gp, dq, loss = _step_deltas_dp(
                U, P, Q, ui, vj, r, conf, cfg, valid, noise)
        else:
            du, gp, dq, loss = _step_deltas(U, P, Q, ui, vj, r, conf, cfg,
                                            valid)
    with jax.named_scope("dmf.local_update"):
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
        # lines 11 + 13-15 via the neighbor table: sender b's gradient gp[b]
        # lands on its S receivers at item vj[b], weighted by the walk weight.
        nb = nbr_idx[ui]                           # (B, S) receiver users
        wb = nbr_wgt[ui]                           # (B, S) walk weights
        if prop_now is not None:
            # straggler rows (prop_now=0): keep only the self slot now
            selfm = (nb == ui[:, None]).astype(wb.dtype)
            wb = wb * jnp.maximum(prop_now[:, None], selfm)
        if recv_gate is not None:
            wb = wb * recv_gate[nb]                # offline receivers get 0
        upd = wb[:, :, None] * gp[:, None, :]      # (B, S, K)
        with jax.named_scope("dmf.p_scatter"):
            P = _p_scatter_add(P, nb, vj, -theta * upd)
        if tele:
            gp2 = jnp.sum(gp * gp, axis=-1)              # (B,)
            selfm_t = (nb == ui[:, None]).astype(wb.dtype)
            scatter_sq = theta * theta * jnp.sum(
                gp2 * jnp.sum(wb * wb, axis=1))
            n_msgs = jnp.sum((wb * (1.0 - selfm_t) > 0).astype(wb.dtype))
            return U, P, Q, loss, gp, jnp.stack(
                [u_sq, q_sq, jnp.sum(gp2), scatter_sq, n_msgs, z, z])
        return U, P, Q, loss, gp
    from repro.robustness import byzantine as byz_lib
    nb = nbr_idx[ui]                               # (B, S) receiver users
    wb = nbr_wgt[ui]                               # (B, S) walk weights
    selfm = (nb == ui[:, None]).astype(wb.dtype)
    # honest line-11 self update (padded tables may carry the self slot
    # more than once at weight 0 — summing the masked weights is exact)
    w_self = jnp.sum(wb * selfm, axis=1)
    if recv_gate is not None:
        w_self = w_self * recv_gate[ui]
    with jax.named_scope("dmf.p_scatter"):
        P = P.at[ui, vj].add(-theta * w_self[:, None] * gp)
    # sender boundary: corrupt the outgoing copy only
    gp_sent = gp
    if amul is not None:
        gp_sent = byz_lib.corrupt_messages(gp, amul, ashill, dirs[ui])
    vj_out = vjm if vjm is not None else vj
    wmsg = wb * (1.0 - selfm)
    if prop_now is not None:
        wmsg = wmsg * prop_now[:, None]
    if recv_gate is not None:
        wmsg = wmsg * recv_gate[nb]
    wmsg_pre = wmsg   # pre-screen delivery weights (telemetry baseline)
    gp_eff = gp_sent
    if byz.screen:
        ok = byz_lib.screen_ok(gp_sent, byz.norm_cap)   # (B,)
        gp_eff = jnp.where(ok[:, None] > 0, gp_sent, 0.0)
        wmsg = wmsg * ok[:, None]
    # 0·NaN = NaN: a zero-weight slot (straggler / offline receiver / padded)
    # whose sender bombed must deliver exactly 0, so the weight gates via
    # `where`, not multiplication. With screening on, gp_eff is already
    # zeroed wherever it was non-finite, so the plain multiply is safe —
    # and ±0 contributions leave the scatter-add bitwise unchanged.
    if byz.screen:
        upd = wmsg[:, :, None] * gp_eff[:, None, :]
    else:
        upd = jnp.where((wmsg > 0)[:, :, None],
                        wmsg[:, :, None] * gp_eff[:, None, :], 0.0)
    if byz.aggregation == "sum":
        with jax.named_scope("dmf.p_scatter"):
            P = P.at[nb, vj_out[:, None]].add(-theta * upd)
        scat = upd
    else:
        b_id, b_pos, b_recv, b_item = bkt
        K = gp.shape[-1]
        vals = upd.reshape(-1, K)
        validity = (wmsg > 0).astype(gp.dtype).reshape(-1)
        comb = byz_lib.robust_combine(
            vals, validity, b_id.reshape(-1), b_pos.reshape(-1),
            b_recv.shape[-1], byz_cap, byz)
        with jax.named_scope("dmf.p_scatter"):
            P = P.at[b_recv, b_item].add(-theta * comb)
        scat = comb
    if tele:
        n_pre = jnp.sum((wmsg_pre > 0).astype(wb.dtype))   # attempted
        n_post = jnp.sum((wmsg > 0).astype(wb.dtype))      # survived screen
        self_sq = jnp.sum((w_self[:, None] * gp) ** 2)
        scatter_sq = theta * theta * (self_sq + jnp.sum(scat * scat))
        return U, P, Q, loss, gp_sent, jnp.stack(
            [u_sq, q_sq, jnp.sum(gp_sent * gp_sent), scatter_sq,
             n_pre, n_post, n_pre - n_post])
    return U, P, Q, loss, gp_sent


def _sparse_batch_update(U, P, Q, nbr_idx, nbr_wgt, ui, vj, r, conf, cfg: DMFConfig,
                         valid=None, rid=None, dp_seed=None, noise=None,
                         tele=False):
    out = _sparse_batch_update_messages(
        U, P, Q, nbr_idx, nbr_wgt, ui, vj, r, conf, cfg, valid, rid, dp_seed,
        noise, tele=tele)
    if tele:
        U, P, Q, loss, _, tvec = out
        return U, P, Q, loss, tvec
    U, P, Q, loss, _ = out
    return U, P, Q, loss


@functools.partial(jax.jit, static_argnames=("cfg", "tele"),
                   donate_argnums=(0, 1, 2))
def _epoch_scan(
    U: jnp.ndarray,
    P: jnp.ndarray,
    Q: jnp.ndarray,
    nbr_idx: jnp.ndarray,      # (I, S)
    nbr_wgt: jnp.ndarray,      # (I, S)
    ui: jnp.ndarray,           # (n_batches, B)
    vj: jnp.ndarray,
    r: jnp.ndarray,
    conf: jnp.ndarray,
    dp_seed: jnp.ndarray,      # () int32 per-epoch mechanism seed (traced)
    cfg: DMFConfig,
    tele: bool = False,        # static: emit the summed TELE_W reductions
):
    """A full epoch as one device-resident `lax.scan` over minibatches —
    one dispatch per epoch instead of a Python loop with a host sync
    (`float(loss)`) per batch. Returns stacked per-batch losses.

    DP (``cfg.dp``): the epoch's ENTIRE noise block is drawn here in one
    vectorized pass over the counter stream — row b·B+k of the stream gets
    `gauss_counter(dp_seed, b·B+k, :)` — and streamed into the scan per
    batch, where the step applies clip + add fused. Per-batch in-step
    generation would pay the log/cos dispatch cost n_batches times for the
    same bits (measured ~50% epoch overhead on CPU vs ~1 noise-gen ms
    amortized). With DP off (the default) `dp_seed` is a dead input XLA
    prunes and the compiled epoch is the exact PR 1 program."""
    nb, B = ui.shape
    from repro.privacy import mechanism
    noise_on = cfg.dp and cfg.mode != "ldmf" and mechanism.noise_std(cfg) > 0
    if noise_on:
        from repro.kernels.dp_noise import gauss_counter
        K = U.shape[-1]
        rid = jnp.arange(nb * B, dtype=jnp.int32).reshape(-1, 1)
        Z = (mechanism.noise_std(cfg)
             * gauss_counter(dp_seed, rid, K)).reshape(nb, B, K)
        xs = (ui, vj, r, conf, Z)
    else:
        xs = (ui, vj, r, conf)

    def body(carry, batch):
        U, P, Q = carry
        b_ui, b_vj, b_r, b_conf = batch[:4]
        out = _sparse_batch_update(
            U, P, Q, nbr_idx, nbr_wgt, b_ui, b_vj, b_r, b_conf, cfg,
            noise=batch[4] if noise_on else None, tele=tele,
        )
        if tele:
            U, P, Q, loss, tvec = out
            return (U, P, Q), (loss, tvec)
        U, P, Q, loss = out
        return (U, P, Q), loss

    (U, P, Q), ys = jax.lax.scan(body, (U, P, Q), xs)
    if tele:
        losses, tvecs = ys
        return U, P, Q, losses, tvecs.sum(axis=0)
    return U, P, Q, ys


@functools.partial(jax.jit,
                   static_argnames=("cfg", "use_ring", "byz", "use_attack",
                                    "byz_cap", "tele"),
                   donate_argnums=(0, 1, 2))
def _epoch_scan_churn(
    U: jnp.ndarray,
    P: jnp.ndarray,
    Q: jnp.ndarray,
    nbr_idx: jnp.ndarray,      # (I, S)
    nbr_wgt: jnp.ndarray,      # (I, S)
    ui: jnp.ndarray,           # (n_batches, B)
    vj: jnp.ndarray,
    r: jnp.ndarray,
    conf: jnp.ndarray,         # offline senders' rows already zeroed
    valid: jnp.ndarray,        # (n_batches, B) sender-online row mask
    prop_now: jnp.ndarray,     # (n_batches, B) full-scatter-this-epoch mask
    recv_gate: jnp.ndarray,    # (I,) receiver-online mask this epoch
    ring_gp: jnp.ndarray,      # (L, n, K) buffered released messages
    ring_ui: jnp.ndarray,      # (L·n,) buffered senders (flattened)
    ring_vj: jnp.ndarray,      # (L·n,) buffered item ids
    ring_deliver: jnp.ndarray,  # (L·n,) float mask: due exactly this epoch
    dp_seed: jnp.ndarray,      # () int32 per-epoch mechanism seed (traced)
    amul: jnp.ndarray,         # (n_batches, B) attack multipliers (dead if !use_attack)
    ashill: jnp.ndarray,       # (n_batches, B) shill-replacement mask
    vjm: jnp.ndarray,          # (n_batches, B) message item addressing
    dirs: jnp.ndarray,         # (I, K) premultiplied shill content
    b_id: jnp.ndarray,         # (n_batches, B, S) bucket ids (dead if sum agg)
    b_pos: jnp.ndarray,        # (n_batches, B, S) in-bucket positions
    b_recv: jnp.ndarray,       # (n_batches, NBK) bucket receiver rows
    b_item: jnp.ndarray,       # (n_batches, NBK) bucket item ids
    cfg: DMFConfig,
    use_ring: bool,
    byz=None,                  # robustness.byzantine.DefenseConfig | None
    use_attack: bool = False,
    byz_cap: int = 0,
    tele: bool = False,        # static: emit the summed TELE_W reductions
):
    """`_epoch_scan` under a fault schedule: same one-dispatch epoch, with
    (1) start-of-epoch delivery of the delay ring's messages due now —
    neighbor slots only (the straggler applied its own line-11 update at
    release), gated by the receivers' online mask NOW; (2) per-row fault
    gates threaded into every minibatch step; (3) the epoch's released
    message stream collected for the ring (only when ``use_ring``).

    Under the trivial schedule (all masks 1, ``use_ring=False``) every
    fault op is a multiply-by-1.0 — bitwise identity — so the compiled
    epoch produces exactly `_epoch_scan`'s outputs.

    Byzantine args (``byz``/``use_attack``/``byz_cap`` static): with
    ``byz=None`` every attack/defense input is statically dead and the
    trace is unchanged. A ring message due now is screened AT DELIVERY —
    a malicious message buffered k epochs ago must not dodge the gate by
    arriving late (the ring buffers SENT, i.e. corrupted, content)."""
    theta = cfg.lr
    if use_ring:
        gflat = ring_gp.reshape(-1, ring_gp.shape[-1])    # (L·n, K)
        nbd = nbr_idx[ring_ui]                            # (L·n, S)
        wbd = nbr_wgt[ring_ui]
        selfm = (nbd == ring_ui[:, None]).astype(wbd.dtype)
        wbd = (wbd * (1.0 - selfm) * recv_gate[nbd]
               * ring_deliver[:, None])
        if byz is not None:
            from repro.robustness import byzantine as byz_lib
            if byz.screen:
                okd = byz_lib.screen_ok(gflat, byz.norm_cap)
                gflat = jnp.where(okd[:, None] > 0, gflat, 0.0)
                wbd = wbd * okd[:, None]
                # screened gflat is finite: plain multiply, ±0-neutral
                upd = wbd[:, :, None] * gflat[:, None, :]
            else:
                upd = jnp.where((wbd > 0)[:, :, None],
                                wbd[:, :, None] * gflat[:, None, :], 0.0)
        else:
            upd = wbd[:, :, None] * gflat[:, None, :]
        P = P.at[nbd, ring_vj[:, None]].add(-theta * upd)
    nb, B = ui.shape
    from repro.privacy import mechanism
    noise_on = cfg.dp and cfg.mode != "ldmf" and mechanism.noise_std(cfg) > 0
    xs = [ui, vj, r, conf, valid, prop_now]
    if noise_on:
        from repro.kernels.dp_noise import gauss_counter
        K = U.shape[-1]
        rid = jnp.arange(nb * B, dtype=jnp.int32).reshape(-1, 1)
        Z = (mechanism.noise_std(cfg)
             * gauss_counter(dp_seed, rid, K)).reshape(nb, B, K)
        xs.append(Z)
    if use_attack:
        xs += [amul, ashill]
    robust = byz is not None and byz.aggregation != "sum"
    if byz is not None:
        xs.append(vjm)
    if robust:
        xs += [b_id, b_pos, b_recv, b_item]

    def body(carry, batch):
        U, P, Q = carry
        b_ui, b_vj, b_r, b_conf, b_val, b_prop = batch[:6]
        i = 6
        b_noise = None
        if noise_on:
            b_noise = batch[i]
            i += 1
        b_amul = b_ashill = b_vjm = bkt = None
        if use_attack:
            b_amul, b_ashill = batch[i], batch[i + 1]
            i += 2
        if byz is not None:
            b_vjm = batch[i]
            i += 1
        if robust:
            bkt = batch[i:i + 4]
        out = _sparse_batch_update_messages(
            U, P, Q, nbr_idx, nbr_wgt, b_ui, b_vj, b_r, b_conf, cfg,
            valid=b_val, noise=b_noise,
            recv_gate=recv_gate, prop_now=b_prop,
            byz=byz, amul=b_amul, ashill=b_ashill,
            dirs=dirs if use_attack else None, vjm=b_vjm, bkt=bkt,
            byz_cap=byz_cap, tele=tele,
        )
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
    tele_sum = None
    if tele:
        ys, tvecs = (ys[:-1], ys[-1])
        tele_sum = tvecs.sum(axis=0)
        ys = ys if use_ring else ys[0]
    if use_ring:
        losses, gps = ys
        out = (U, P, Q, losses, gps)
    else:
        out = (U, P, Q, ys, None)
    if tele:
        return out + (tele_sum,)
    return out


def train_epoch_churn(
    state: DMFState,
    prop,
    train: np.ndarray,
    cfg: DMFConfig,
    rng: np.random.Generator,
    t: int,
    plan,                       # robustness.faults.ChurnPlan
    ring,                       # robustness.faults.DelayRing | None
    accountant=None,
    attack=None,                # robustness.byzantine.AttackPlan | None
    byz=None,                   # robustness.byzantine.DefenseConfig | None
    tele: bool = False,         # append the epoch's TELE_W device stats
) -> tuple[DMFState, float]:
    """`train_epoch` under a compiled `ChurnPlan` for epoch ``t``: the SAME
    sampled stream (same rng consumption, per-epoch DP seed included), with
    offline senders' rows zeroed host-side (conf=0 + valid=0 ⇒ their U/Q
    rows bit-frozen and they release nothing), receivers gated by this
    epoch's online mask, stragglers' neighbor scatters deferred through
    ``ring``, and the accountant observing only the REALIZED stream.
    Reported loss normalizes by realized (online) rows. ``cfg.n_shards>1``
    dispatches to the SPMD counterpart (sharding/dmf.py).

    ``attack`` (a compiled `AttackPlan`) corrupts the epoch's outgoing
    messages at the sender boundary; ``byz`` (a `DefenseConfig`) turns on
    receiver-side screening / robust aggregation. Both None (the default)
    leaves the compiled epoch untouched. The delay ring buffers the SENT
    (post-corruption) stream under shill re-addressing (``vjm``)."""
    if cfg.n_shards > 1:
        from repro.sharding import dmf as sharded_dmf
        return sharded_dmf.train_epoch_churn_sharded(
            state, prop, train, cfg, rng, t, plan, ring,
            accountant=accountant, attack=attack, byz=byz, tele=tele)
    nbr = _as_neighbor_table(prop)
    ui, vj, r, conf = sample_epoch(train, cfg, rng)
    B = cfg.batch_size
    nb = len(ui) // B
    n = nb * B
    shape = (nb, B)
    ui2 = ui[:n].reshape(shape)
    vj2 = vj[:n].reshape(shape)
    _, dp_seed = epoch_dp_inputs(cfg, rng, n)
    on, sender_on, prop_now, due = plan.epoch_row_masks(t, ui2)
    conf2 = conf[:n].reshape(shape) * sender_on
    if accountant is not None:
        accountant.observe_epoch(ui2, valid=sender_on)
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
        ring_gp = jnp.zeros((1, 1, state.U.shape[-1]), jnp.float32)
    use_attack = attack is not None
    if use_attack:
        assert byz is not None   # fit() supplies DefenseConfig() (all-off)
        amul, ashill, vjm = attack.epoch_row_attack(
            t, ui2, vj2, sender_on=sender_on)
        dirs = jnp.asarray(attack.dirs)
    else:
        amul = ashill = np.zeros(1, np.float32)
        vjm = vj2
        dirs = jnp.zeros((1, state.U.shape[-1]), jnp.float32)
    robust = byz is not None and byz.aggregation != "sum"
    if robust:
        from repro.robustness import byzantine as byz_lib
        groups = byz_lib.group_messages(
            ui2, vjm, nbr.idx, nbr.wgt, cfg.n_items,
            sender_gate=sender_on.astype(bool) & prop_now.astype(bool),
            recv_on=on.astype(bool))
        gb = (jnp.asarray(groups.bucket_id), jnp.asarray(groups.pos),
              jnp.asarray(groups.recv), jnp.asarray(groups.item))
        byz_cap = groups.cap
    else:
        z1 = np.zeros(1, np.int32)
        gb = (z1, z1, z1, z1)
        byz_cap = 0
    out = _epoch_scan_churn(
        state.U, state.P, state.Q, nbr.idx, nbr.wgt,
        jnp.asarray(ui2), jnp.asarray(vj2),
        jnp.asarray(r[:n].reshape(shape)), jnp.asarray(conf2),
        jnp.asarray(sender_on.astype(np.float32)),
        jnp.asarray(prop_now.astype(np.float32)),
        jnp.asarray(on.astype(np.float32)),
        ring_gp, jnp.asarray(r_ui), jnp.asarray(r_vj), jnp.asarray(r_del),
        jnp.asarray(dp_seed, jnp.int32),
        jnp.asarray(amul), jnp.asarray(ashill), jnp.asarray(vjm), dirs,
        gb[0], gb[1], gb[2], gb[3],
        cfg, use_ring, byz, use_attack, byz_cap, tele=tele,
    )
    U, P, Q, losses, gps = out[:5]
    if use_ring:
        ring.write(t, gps.reshape(n, -1), ui2,
                   vjm if byz is not None else vj2, due)
    total = float(np.asarray(losses, dtype=np.float64).sum())
    realized = int(sender_on.sum())
    l = total / max(realized, 1)
    if tele:
        return DMFState(U, P, Q), l, np.asarray(out[5])
    return DMFState(U, P, Q), l


def sample_with_negatives(
    pos: np.ndarray, n_items: int, m: int, rng: np.random.Generator
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Positives + m sampled unobserved negatives per positive with
    confidence 1/m (paper §Unobserved rating sample), shuffled together.
    The single definition of the sampling convention — shared by training
    epochs and the online-refresh event stream (serving/online.py), so the
    two objectives cannot silently diverge."""
    n = len(pos)
    neg_u = np.repeat(pos[:, 0], m)
    neg_j = rng.integers(0, n_items, size=n * m)
    ui = np.concatenate([pos[:, 0], neg_u])
    vj = np.concatenate([pos[:, 1], neg_j])
    r = np.concatenate([np.ones(n, np.float32), np.zeros(n * m, np.float32)])
    conf = np.concatenate(
        [np.ones(n, np.float32), np.full(n * m, 1.0 / m, np.float32)]
    )
    order = rng.permutation(len(ui))
    return ui[order], vj[order], r[order], conf[order]


def sample_epoch(
    train: np.ndarray, cfg: DMFConfig, rng: np.random.Generator
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Shuffled positives + m sampled unobserved negatives with confidence 1/m."""
    pos = train[rng.permutation(len(train))]
    return sample_with_negatives(pos, cfg.n_items, cfg.neg_samples, rng)


def train_epoch_dense(
    state: DMFState,
    M: jnp.ndarray,
    train: np.ndarray,
    cfg: DMFConfig,
    rng: np.random.Generator,
) -> tuple[DMFState, float]:
    """Seed reference path: Python per-batch loop over the dense (I, I) M,
    with a host sync per batch. O(I·B·K) per batch — kept as the
    equivalence oracle for the sparse-scan path and for ablations."""
    ui, vj, r, conf = sample_epoch(train, cfg, rng)
    B = cfg.batch_size
    n = (len(ui) // B) * B
    U, P, Q = state.U, state.P, state.Q
    total = 0.0
    for s in range(0, n, B):
        U, P, Q, loss = _batch_step(
            U, P, Q, M,
            jnp.asarray(ui[s : s + B]),
            jnp.asarray(vj[s : s + B]),
            jnp.asarray(r[s : s + B]),
            jnp.asarray(conf[s : s + B]),
            cfg,
        )
        total += float(loss)
    return DMFState(U, P, Q), total / max(n, 1)


def _as_neighbor_table(prop) -> graph_lib.NeighborTable:
    if isinstance(prop, graph_lib.NeighborTable):
        return prop
    return graph_lib.neighbor_table_from_dense(np.asarray(prop))


def epoch_dp_inputs(cfg: DMFConfig, rng: np.random.Generator, n: int):
    """Per-epoch DP mechanism inputs for an n-row stream: the rows' global
    stream ids (the shard-count-invariant noise keys) and the fresh
    per-epoch seed. DP off: zeros, and — crucially — NO rng draw, so the
    un-noised paths' rng stream stays bit-exact."""
    rid = np.arange(n, dtype=np.int32)
    if not cfg.dp:
        return rid, 0
    from repro.privacy import mechanism
    return rid, mechanism.epoch_noise_seed(rng, cfg)


def train_epoch(
    state: DMFState,
    prop,                       # graph.NeighborTable, or dense (I, I) M
    train: np.ndarray,
    cfg: DMFConfig,
    rng: np.random.Generator,
    accountant=None,
    tele: bool = False,         # append the epoch's TELE_W device stats
) -> tuple[DMFState, float]:
    """Sparse-neighborhood scan epoch: one jitted dispatch for the whole
    epoch, O(B·S·K) propagation per batch. Passing a dense M converts it
    per call — convert once via `graph.walk_neighbor_table` in loops.

    With ``cfg.n_shards > 1`` the epoch runs learner-sharded: same minibatch
    stream, rows routed to each user's home shard, one SPMD dispatch over
    the ``learners`` mesh (sharding/dmf.py). The returned state's learner
    axis stays padded+sharded between epochs; `fit` unpads at the end, or
    call `sharding.dmf.unpad_state` yourself.

    ``accountant`` (a `privacy.GaussianAccountant`) observes the epoch's
    realized minibatch stream for per-learner ε(δ) tracking when DP is on.
    """
    if cfg.n_shards > 1:
        from repro.sharding import dmf as sharded_dmf
        return sharded_dmf.train_epoch_sharded(
            state, prop, train, cfg, rng, accountant=accountant, tele=tele)
    nbr = _as_neighbor_table(prop)
    with trace_lib.span("fit.sample"):
        ui, vj, r, conf = sample_epoch(train, cfg, rng)
    B = cfg.batch_size
    nb = len(ui) // B
    n = nb * B
    shape = (nb, B)
    _, dp_seed = epoch_dp_inputs(cfg, rng, n)
    if accountant is not None:
        accountant.observe_epoch(ui[:n].reshape(shape))
    with trace_lib.span("fit.h2d"):
        batches = [jnp.asarray(a[:n].reshape(shape)) for a in (ui, vj, r, conf)]
    with trace_lib.span("fit.launch"):
        out = _epoch_scan(
            state.U, state.P, state.Q, nbr.idx, nbr.wgt, *batches,
            jnp.asarray(dp_seed, jnp.int32), cfg, tele=tele,
        )
    U, P, Q, losses = out[:4]
    with trace_lib.span("fit.loss_sync"):
        total = float(np.asarray(losses, dtype=np.float64).sum())
    l = total / max(n, 1)
    if tele:
        return DMFState(U, P, Q), l, np.asarray(out[4])
    return DMFState(U, P, Q), l


@functools.partial(jax.jit, static_argnames=())
def scores(state_U: jnp.ndarray, state_P: jnp.ndarray, state_Q: jnp.ndarray) -> jnp.ndarray:
    """(I, J) predicted preference û_i^T (p^i_j + q^i_j) — computed on-device
    per learner in deployment; materialized densely here for evaluation."""
    V = state_P + state_Q                     # (I, J, K)
    return jnp.einsum("ik,ijk->ij", state_U, V)


def test_loss(state: DMFState, test: np.ndarray) -> float:
    u = state.U[test[:, 0]]
    v = state.P[test[:, 0], test[:, 1]] + state.Q[test[:, 0], test[:, 1]]
    pred = jnp.sum(u * v, -1)
    return float(0.5 * jnp.mean((1.0 - pred) ** 2))


@dataclasses.dataclass
class FitResult:
    state: DMFState
    train_losses: list
    test_losses: list
    privacy: dict | None = None   # accountant summary when cfg.dp (ε(δ) etc.)
    diverged_at: int | None = None  # epoch whose update went non-finite
                                    # (only set under on_nonfinite="halt")
    telemetry: list | None = None   # per-epoch event dicts when
                                    # fit(telemetry=True) (obs/telemetry.py)


class DivergenceError(RuntimeError):
    """Training produced a non-finite loss or factor update
    (``fit(on_nonfinite="raise")``)."""


def _epoch_finite(state: DMFState, loss: float) -> bool:
    """Epoch health check: loss AND factors finite. Three all-reduces —
    only paid under on_nonfinite={"raise","halt"}."""
    if not np.isfinite(loss):
        return False
    return bool(jnp.isfinite(state.U).all() & jnp.isfinite(state.P).all()
                & jnp.isfinite(state.Q).all())


def fit(
    cfg: DMFConfig,
    train: np.ndarray,
    M: np.ndarray,
    epochs: int = 30,
    test: np.ndarray | None = None,
    callback: Callable | None = None,
    seed: int | None = None,
    dense_reference: bool = False,
    dp_delta: float = 1e-5,
    churn=None,
    checkpoint_dir=None,
    checkpoint_every: int = 0,
    resume_from=None,
    attack=None,
    defense=None,
    on_nonfinite: str = "warn",
    telemetry: bool = False,
    telemetry_out=None,
    log_every: int = 0,
) -> FitResult:
    """Train `epochs` epochs of Alg. 1. `M` may be a dense (I, I) propagation
    matrix or a `graph.NeighborTable`; the sparse scan path is the default,
    `dense_reference=True` forces the seed dense per-batch loop (oracle).

    With DP on (``cfg.dp_sigma > 0``) a `privacy.GaussianAccountant`
    observes every epoch's realized minibatch stream; its per-learner
    ε(``dp_delta``) summary lands in `FitResult.privacy`.

    Fault tolerance (robustness/): ``churn`` is a `ChurnConfig` (compiled
    here) or pre-compiled `ChurnPlan` — epochs then run the fault-injected
    path (offline learners bit-frozen, stragglers' messages delivered
    late). ``checkpoint_dir`` + ``checkpoint_every`` snapshot the FULL loop
    state (factors, rng stream, delay ring, accountant) every N completed
    epochs; ``resume_from`` (a step dir or checkpoint root) restores one
    and continues — bit-identical to the uninterrupted run, DP included
    (the counter-keyed noise replays from the restored rng stream).

    Byzantine robustness (robustness/byzantine.py): ``attack`` is an
    `AttackConfig` (compiled here) or pre-compiled `AttackPlan` injecting
    malicious outgoing messages; ``defense`` is a `DefenseConfig` turning
    on receiver-side screening and/or robust aggregation. Either one
    routes epochs through the churn machinery (a trivial all-online plan
    when ``churn`` is None); both None leaves every compiled program
    bit-exact with the defenseless stack.

    Observability (obs/, DESIGN.md §14): ``telemetry=True`` (or a
    ``telemetry_out`` JSONL path) collects one event dict per epoch —
    loss, update norms, released/scattered message mass, message counts
    per shard, DP ε-so-far, churn online count, delay-ring occupancy,
    screening accept/reject — into `FitResult.telemetry`. The device
    half is read-only reductions inside the same one-dispatch epoch (no
    rng draws): factor trajectories are bit-identical with telemetry
    off, which in turn compiles the exact uninstrumented program.
    ``log_every=N`` logs a progress line every N epochs via
    ``logging.getLogger("repro.dmf")`` (includes ε when DP is on); span
    tracing is global — see `obs.trace.configure_tracing`.

    ``on_nonfinite`` — divergence sentinel: "warn" (default) emits a
    RuntimeWarning on a non-finite epoch loss and keeps going (the
    pre-existing numerics); "raise" raises `DivergenceError`; "halt"
    stops training, returns the LAST finite state and sets
    `FitResult.diverged_at` to the offending epoch (that epoch's loss
    stays in `train_losses` as the evidence)."""
    assert on_nonfinite in ("warn", "raise", "halt"), on_nonfinite
    tele_on = bool(telemetry) or telemetry_out is not None
    assert not (tele_on and dense_reference), (
        "telemetry rides the sparse/sharded epoch programs")
    rng = np.random.default_rng(cfg.seed if seed is None else seed)
    accountant = None
    if cfg.dp and cfg.dp_sigma > 0.0:   # ldmf: no releases, no ε claim
        from repro.privacy import GaussianAccountant
        accountant = GaussianAccountant(
            n_users=cfg.n_users, sigma=cfg.dp_sigma, delta=dp_delta)
    plan = None
    ring = None
    if churn is not None:
        from repro.robustness import faults
        assert not dense_reference, "churn runs the sparse/sharded paths"
        plan = (churn.compile(cfg.n_users, epochs)
                if isinstance(churn, faults.ChurnConfig) else churn)
        assert plan.n_users == cfg.n_users, (plan.n_users, cfg.n_users)
        assert plan.n_epochs >= epochs, (plan.n_epochs, epochs)
        # the per-epoch stream length is schedule-independent, so the ring
        # shape is known up front
        nb = (len(train) * (1 + cfg.neg_samples)) // cfg.batch_size
        ring = faults.DelayRing.create(plan.k_max, nb * cfg.batch_size,
                                       cfg.dim)
    attack_plan = None
    byz = None
    if attack is not None:
        from repro.robustness import byzantine
        attack_plan = (attack.compile(cfg.n_users, epochs, cfg.dim)
                       if isinstance(attack, byzantine.AttackConfig)
                       else attack)
        assert attack_plan.n_users == cfg.n_users, (
            attack_plan.n_users, cfg.n_users)
        assert attack_plan.n_epochs >= epochs, (attack_plan.n_epochs, epochs)
        assert attack_plan.config.target_item < cfg.n_items
        if attack_plan.is_trivial():
            attack_plan = None
    if defense is not None and defense.active:
        byz = defense
    if attack_plan is not None and byz is None:
        from repro.robustness.byzantine import DefenseConfig
        byz = DefenseConfig()    # undefended channel, byz path on
    if (attack_plan is not None or byz is not None) and plan is None:
        # the byzantine exchange runs on the churn epoch program — use the
        # trivial all-online schedule (bit-exact gates), no delay ring
        from repro.robustness import faults
        assert not dense_reference, "byzantine runs the sparse/sharded paths"
        plan = faults.no_churn(cfg.n_users, epochs)
    if dense_reference:
        assert not isinstance(M, graph_lib.NeighborTable), (
            "dense_reference needs the dense M"
        )
        assert cfg.n_shards == 1, "dense_reference is the single-device oracle"
        assert not cfg.dp, "dense_reference is the un-noised oracle path"
    with trace_lib.span("fit.init"):
        state = init_state(cfg, rng)     # rng's first draws: none above
        if dense_reference:
            prop = jnp.asarray(M)
            epoch_fn = train_epoch_dense
        elif cfg.n_shards > 1:
            from repro.sharding import dmf as sharded_dmf
            prop = sharded_dmf.make_shard_plan(_as_neighbor_table(M), cfg)
            epoch_fn = train_epoch
        else:
            prop = _as_neighbor_table(M)
            epoch_fn = train_epoch
    collector = None
    if tele_on:
        from repro.obs import telemetry as tele_lib
        collector = tele_lib.EpochCollector(jsonl_path=telemetry_out,
                                            n_shards=cfg.n_shards)
    logger = None
    if log_every:
        import logging
        logger = logging.getLogger("repro.dmf")
    tr_losses, te_losses = [], []
    start = 0
    if resume_from is not None:
        from repro.robustness import recovery
        state, rng, ring, start, tr_losses, te_losses = (
            recovery.load_training(resume_from, like_state=state,
                                   ring=ring, accountant=accountant))
    diverged_at = None
    warned = False
    for t in range(start, epochs):
        if on_nonfinite == "halt":
            # donated buffers: the epoch consumes `state`, so the fallback
            # copy must be taken up front (only paid in halt mode)
            prev = DMFState(jnp.copy(state.U), jnp.copy(state.P),
                            jnp.copy(state.Q))
        t0 = time.perf_counter() if tele_on else 0.0
        dstats = None
        with trace_lib.span("fit.epoch", epoch=t):
            if plan is not None:
                out = train_epoch_churn(state, prop, train, cfg, rng, t,
                                        plan, ring, accountant=accountant,
                                        attack=attack_plan, byz=byz,
                                        tele=tele_on)
            elif epoch_fn is train_epoch_dense:
                out = epoch_fn(state, prop, train, cfg, rng)
            else:
                out = epoch_fn(state, prop, train, cfg, rng,
                               accountant=accountant, tele=tele_on)
        if tele_on:
            state, l, dstats = out
        else:
            state, l = out
        tr_losses.append(l)
        if on_nonfinite == "warn":
            if not warned and not np.isfinite(l):
                import warnings
                warnings.warn(
                    f"epoch {t}: non-finite training loss {l!r} — training "
                    "has diverged (see fit(on_nonfinite=...))",
                    RuntimeWarning, stacklevel=2)
                warned = True
        elif not _epoch_finite(state, l):
            if on_nonfinite == "raise":
                raise DivergenceError(
                    f"epoch {t}: non-finite loss or factors (loss={l!r})")
            state = prev             # halt: last finite state wins
            diverged_at = t
            break
        if test is not None:
            te_losses.append(test_loss(state, test))
        if collector is not None:
            collector.record(
                t, train_loss=l, device_stats=dstats,
                test_loss=te_losses[-1] if test is not None else None,
                accountant=accountant, plan=plan, ring=ring, byz=byz,
                wall_s=time.perf_counter() - t0)
        if logger is not None and ((t + 1) % log_every == 0
                                   or t == epochs - 1):
            msg = f"epoch {t + 1}/{epochs} train_loss={l:.6f}"
            if test is not None:
                msg += f" test_loss={te_losses[-1]:.6f}"
            if accountant is not None and accountant.eps_trajectory:
                msg += f" eps={accountant.eps_trajectory[-1]:.4f}"
            logger.info(msg)
        if callback is not None:
            callback(t, state, l)
        if (checkpoint_dir is not None and checkpoint_every > 0
                and (t + 1) % checkpoint_every == 0):
            from repro.robustness import recovery
            snap = state
            if cfg.n_shards > 1:
                from repro.sharding import dmf as sharded_dmf
                snap = sharded_dmf.unpad_state(state, cfg.n_users)
            recovery.save_training(
                checkpoint_dir, step=t + 1, state=snap, rng=rng, ring=ring,
                accountant=accountant, train_losses=tr_losses,
                test_losses=te_losses)
    if cfg.n_shards > 1 and not dense_reference:
        from repro.sharding import dmf as sharded_dmf
        state = sharded_dmf.unpad_state(state, cfg.n_users)
    if collector is not None:
        collector.close()
    return FitResult(state, tr_losses, te_losses,
                     privacy=accountant.summary() if accountant else None,
                     diverged_at=diverged_at,
                     telemetry=collector.events if collector else None)


def evaluate(
    state: DMFState, train: np.ndarray, test: np.ndarray, n_users: int, n_items: int,
    ks=(5, 10), n_shards: int = 1,
    chunk_users: int | None = None,
) -> dict[str, float]:
    """Ranking metrics via the streaming top-k kernel: the (I, J) score
    matrix never materializes — per-user running top-k is carried across
    item tiles (ops.recommend_topk_peruser). ``n_shards > 1`` runs the
    kernel learner-sharded over the mesh (row-parallel, same results).

    ``chunk_users`` streams the USER axis too: each chunk builds only its
    own V = P + Q rows and train/test mask rows (O(chunk · J) peak, from
    the interaction pairs directly), so the full (I, J, K) V view, the
    (I, J) masks and the factors never co-materialize — the regime that
    makes evaluation feasible when I is in the millions while the (I, S)
    neighbor table from training is still resident. Per-user hit counts
    are integers and the final reduction sees them in the same global user
    order, so results are IDENTICAL floats to the unchunked path."""
    from repro.kernels import ops
    if n_shards > 1:
        from repro.sharding import dmf as sharded_dmf
        return sharded_dmf.evaluate_sharded(
            state, train, test, n_users, n_items, n_shards, ks=ks,
            chunk_users=chunk_users)
    kmax = max(ks)
    if chunk_users is None:
        train_mask = metrics_lib.masks_from_interactions(n_users, n_items, train)
        test_mask = metrics_lib.masks_from_interactions(n_users, n_items, test)
        V = state.P + state.Q                 # (I, J, K) per-learner factors
        _, idx = ops.recommend_topk_peruser(
            state.U, V, jnp.asarray(train_mask), kmax)
        return metrics_lib.evaluate_ranking_from_topk(
            np.asarray(idx), test_mask, ks)
    hits: dict[int, list[np.ndarray]] = {k: [] for k in ks}
    n_test_parts: list[np.ndarray] = []
    step = max(int(chunk_users), 1)
    for s in range(0, n_users, step):
        e = min(s + step, n_users)
        tm = metrics_lib.masks_from_interactions_rows(s, e - s, n_items, train)
        ts = metrics_lib.masks_from_interactions_rows(s, e - s, n_items, test)
        V = state.P[s:e] + state.Q[s:e]       # only this chunk's item view
        _, idx = ops.recommend_topk_peruser(
            state.U[s:e], V, jnp.asarray(tm), kmax)
        rec = np.asarray(idx)
        for k in ks:
            hits[k].append(metrics_lib.topk_hits(rec, ts, k))
        n_test_parts.append(ts.sum(axis=1))
    n_test = np.concatenate(n_test_parts) if n_test_parts else np.zeros(0, int)
    out = {}
    for k in ks:
        p, r = metrics_lib.precision_recall_from_hits(
            np.concatenate(hits[k]) if hits[k] else np.zeros(0, int), n_test, k)
        out[f"P@{k}"] = p
        out[f"R@{k}"] = r
    return out


def evaluate_dense(
    state: DMFState, train: np.ndarray, test: np.ndarray, n_users: int, n_items: int,
    ks=(5, 10),
) -> dict[str, float]:
    """Seed reference evaluation through the dense (I, J) score matrix —
    oracle for the streaming path."""
    sc = np.asarray(scores(state.U, state.P, state.Q))
    train_mask = metrics_lib.masks_from_interactions(n_users, n_items, train)
    test_mask = metrics_lib.masks_from_interactions(n_users, n_items, test)
    return metrics_lib.evaluate_ranking(sc, train_mask, test_mask, ks)
