"""jit'd public wrappers around the Pallas kernels (padding + dispatch).

Every wrapper takes ``interpret=None``, resolved by
`repro.kernels.resolve_interpret` from the backend: compiled by Mosaic on a
TPU, the Pallas interpreter elsewhere (the CPU test suite). An explicit
``interpret=False`` lowers for a TPU from any process, which is how
tests/test_tpu_compile.py compiles these kernels for a described v5e chip.
All wrappers pad to MXU/lane alignment (128) and slice back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dmf_update, dp_noise, gossip_mix, topk_scores
from repro.kernels import serve_topk as serve_topk_lib

LANE = 128


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "gamma", "interpret"))
def dmf_grads(u, p, q, r, conf, *, alpha: float, beta: float, gamma: float,
              interpret: bool | None = None):
    """Fused Eqs. 9-11. u/p/q: (B, K); r/conf: (B,)."""
    B, K = u.shape
    block_b = 256 if B % 256 == 0 else (B if B <= 256 else None)
    if block_b is None:
        # pad batch to a multiple of 256; padded rows have conf=0 (no-op grads
        # except the regularizer on zero factors = 0)
        u, p, q = (_pad_to(x, 256, 0) for x in (u, p, q))
        r = _pad_to(r, 256, 0)
        conf = _pad_to(conf, 256, 0)
        block_b = 256
    Bp = u.shape[0]
    uP, pP, qP = (_pad_to(x, LANE, 1) for x in (u, p, q))
    gu, gp, gq = dmf_update.dmf_grads_kernel_call(
        uP, pP, qP, r, conf, alpha=alpha, beta=beta, gamma=gamma,
        block_b=block_b, interpret=interpret,
    )
    return gu[:B, :K], gp[:B, :K], gq[:B, :K]


@functools.partial(jax.jit, static_argnames=("theta", "alpha", "beta", "gamma",
                                             "interpret"))
def dmf_fused_step(u, p, q, r, conf, *, theta: float, alpha: float, beta: float,
                   gamma: float, interpret: bool | None = None):
    """Fused Alg. 1 step: Eqs. 9-11 grads, lr-scaled u/q deltas, raw p
    message, batch loss — one kernel pass. u/p/q: (B, K); r/conf: (B,).
    Returns (du, gp, dq, loss_scalar)."""
    B, K = u.shape
    block_b = 256 if B % 256 == 0 else (B if B <= 256 else None)
    if block_b is None:
        # pad batch to a multiple of 256; padded rows carry conf=0 and zero
        # factors, so grads, deltas and loss contributions are all exactly 0
        u, p, q = (_pad_to(x, 256, 0) for x in (u, p, q))
        r = _pad_to(r, 256, 0)
        conf = _pad_to(conf, 256, 0)
        block_b = 256
    uP, pP, qP = (_pad_to(x, LANE, 1) for x in (u, p, q))
    du, gp, dq, loss = dmf_update.dmf_fused_step_kernel_call(
        uP, pP, qP, r, conf, theta=theta, alpha=alpha, beta=beta, gamma=gamma,
        block_b=block_b, interpret=interpret,
    )
    return du[:B, :K], gp[:B, :K], dq[:B, :K], loss[0, 0]


@functools.partial(jax.jit, static_argnames=("theta", "alpha", "beta", "gamma",
                                             "clip", "interpret"))
def dmf_fused_step_dp(u, p, q, r, conf, z, *, theta: float, alpha: float,
                      beta: float, gamma: float, clip: float,
                      interpret: bool | None = None):
    """`dmf_fused_step` with the DP mechanism folded into the SAME kernel
    pass: the returned gp message is already clipped to ``clip`` and
    perturbed with ``z`` — the batch's pre-scaled σC noise block from the
    counter-keyed stream (generated once per epoch, see core/dmf.py). The
    DP training hot path keeps the un-noised path's dispatch count — one
    fused kernel per minibatch."""
    B, K = u.shape
    block_b = 256 if B % 256 == 0 else (B if B <= 256 else None)
    if block_b is None:
        # padded rows carry conf=0 + zero factors + zero noise:
        # grads/deltas/loss are 0 and the clip scale is 1
        u, p, q, z = (_pad_to(x, 256, 0) for x in (u, p, q, z))
        r = _pad_to(r, 256, 0)
        conf = _pad_to(conf, 256, 0)
        block_b = 256
    uP, pP, qP, zP = (_pad_to(x, LANE, 1) for x in (u, p, q, z))
    du, gp, dq, loss = dmf_update.dmf_fused_step_dp_kernel_call(
        uP, pP, qP, r, conf, zP, theta=theta, alpha=alpha, beta=beta,
        gamma=gamma, clip=clip, block_b=block_b, interpret=interpret,
    )
    return du[:B, :K], gp[:B, :K], dq[:B, :K], loss[0, 0]


@functools.partial(jax.jit, static_argnames=("clip", "noise_std", "interpret"))
def dp_clip_noise(g, rid, seed, *, clip: float, noise_std: float,
                  interpret: bool | None = None):
    """Fused DP mechanism for gradient messages: per-row L2 clip to
    ``clip`` + additive N(0, noise_std²) counter-keyed Gaussian noise, one
    kernel pass (kernels/dp_noise.py). g: (B, K) f32; rid: (B,) int32
    global message-row ids; seed: int32 scalar (traced — changing the
    per-epoch seed does not recompile). ``clip=inf`` scales by exactly 1.0
    and ``noise_std=0`` compiles the noise path out entirely, so the
    disabled mechanism is bit-exact identity."""
    B, K = g.shape
    block_b = 256 if B % 256 == 0 else (B if B <= 256 else None)
    if block_b is None:
        # padded rows carry g=0 (clip scale 1) and their noise is sliced off
        g = _pad_to(g, 256, 0)
        rid = _pad_to(rid, 256, 0)
        block_b = 256
    gP = _pad_to(g, LANE, 1)      # zero K-pad: row norms unchanged
    seed2 = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    out = dp_noise.dp_clip_noise_kernel_call(
        gP, rid.astype(jnp.int32), seed2, clip=clip, noise_std=noise_std,
        n_real=K, block_b=block_b, interpret=interpret,
    )
    return out[:B, :K]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gossip_mix_op(M, X, *, interpret: bool | None = None):
    """Y = M @ X with MXU tiling. M: (I, I); X: (I, F)."""
    I, F = X.shape
    Mp = _pad_to(_pad_to(M.astype(jnp.float32), LANE, 0), LANE, 1)
    Xp = _pad_to(_pad_to(X.astype(jnp.float32), LANE, 0), LANE, 1)
    Y = gossip_mix.gossip_mix_kernel_call(Mp, Xp, interpret=interpret)
    return Y[:I, :F]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def recommend_topk(U, V, train_mask, k: int, *, interpret: bool | None = None):
    """Masked top-k recommendation; never materializes (I, J) in HBM."""
    I, K = U.shape
    J = V.shape[0]
    Up = _pad_to(_pad_to(U.astype(jnp.float32), LANE, 0), LANE, 1)
    Vp = _pad_to(_pad_to(V.astype(jnp.float32), 256, 0), LANE, 1)
    # padded users: mask=0 rows are fine (garbage rows sliced off);
    # padded items must be masked out
    mp = _pad_to(_pad_to(train_mask.astype(jnp.int8), 256, 1), LANE, 0)
    if mp.shape[1] > J:
        mp = mp.at[:, J:].set(1)
    vals, idx = topk_scores.topk_scores_kernel_call(
        Up, Vp, mp, k, interpret=interpret,
    )
    return vals[:I], idx[:I]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def serve_topk(U, V, cand, seen, k: int, *, interpret: bool | None = None):
    """Geo-pruned batched serving: per-request candidate gather + scores +
    running top-k fused (kernels/serve_topk.py). U: (R, K); V: (R, J, K)
    per-request item factors; cand: (R, Cw) int32 candidate item ids, -1
    padded; seen: (R, J) bool/int8 seen-filter. Returns (vals, idx) (R, k),
    idx = global item ids, -1 in unfilled slots.

    *Compute* per request is O(Cw·K), not O(J·K) — the grid tiles the
    candidate dim. Memory staging is still
    O(J·K) per request (the user's full item slab is handed to the kernel
    as the gather source); the compiled-TPU design keeps V in HBM and DMAs
    only the candidate rows, making the traffic O(Cw·K) too. Padding: R to
    the request block, K to the f32 sublane quantum, J to the lane (never
    gathered: cand ids < J), Cw to the candidate block with -1 (masked
    inside the kernel)."""
    R, K = U.shape
    J = V.shape[1]
    BI, BJ = 8, 128
    Up = _pad_to(_pad_to(U.astype(jnp.float32), BI, 0), 8, 1)
    Vt = jnp.transpose(V.astype(jnp.float32), (0, 2, 1))   # (R, K, J)
    Vt = _pad_to(_pad_to(_pad_to(Vt, BI, 0), 8, 1), LANE, 2)
    sp = _pad_to(_pad_to(seen.astype(jnp.int8), LANE, 1), BI, 0)
    cp = jnp.pad(cand.astype(jnp.int32),
                 [(0, (-R) % BI), (0, (-cand.shape[1]) % BJ)],
                 constant_values=-1)
    vals, idx = serve_topk_lib.serve_topk_kernel_call(
        Up, Vt, sp, cp, k, block_i=BI, block_j=BJ, interpret=interpret,
    )
    return vals[:R], idx[:R]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def serve_topk_window(U, Vw, cand, seen_w, k: int, *, interpret: bool | None = None):
    """Tiled geo-pruned serving over pre-gathered candidate windows — the
    million-scale replacement for `serve_topk`'s per-request full item slab.
    U: (R, K); Vw: (R, Cw, K) the candidate windows' item factors (row r is
    the user's v^i at exactly the `cand[r]` ids, any values in padded
    slots); cand: (R, Cw) int32 candidate ids, -1 padded; seen_w: (R, Cw)
    bool/int8 seen bits aligned to `cand`. Returns (vals, idx) (R, k),
    idx = global item ids, -1 in unfilled slots.

    Both compute AND staging are O(Cw·K) per request: the kernel's grid
    streams (8, K, 128) window tiles, never touching J, so the factor
    source (the (I, cap, K) store slab, or V rows) stays HBM-resident.
    Bitwise identical to `serve_topk` on the same candidates: identical
    block sizes (8, 128), K zero-padding, K-major contraction and
    running-top-k carry — pinned by tests on tie-heavy inputs."""
    R, K = U.shape
    Cw = cand.shape[1]
    BI, BJ = 8, 128
    Up = _pad_to(_pad_to(U.astype(jnp.float32), BI, 0), 8, 1)
    Vt = jnp.transpose(Vw.astype(jnp.float32), (0, 2, 1))   # (R, K, Cw)
    Vt = _pad_to(_pad_to(_pad_to(Vt, BI, 0), 8, 1), LANE, 2)
    sp = _pad_to(_pad_to(seen_w.astype(jnp.int8), LANE, 1), BI, 0)
    cp = jnp.pad(cand.astype(jnp.int32),
                 [(0, (-R) % BI), (0, (-Cw) % BJ)],
                 constant_values=-1)
    vals, idx = serve_topk_lib.serve_topk_window_kernel_call(
        Up, Vt, sp, cp, k, block_i=BI, block_j=BJ, interpret=interpret,
    )
    return vals[:R], idx[:R]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def serve_topk_window_quant(U, Vq, scale, cand, seen_w, k: int, *,
                            interpret: bool | None = None):
    """Quantized `serve_topk_window`: candidate windows as int8 codes with a
    per-request dequant scale (codes·scale ≈ v), or bf16 factors with
    scale = 1.0. Vq: (R, Cw, K) int8/bf16; scale: (R,) f32. Dequantization
    runs in-VMEM per tile; everything downstream (contraction, masking,
    top-k carry, tie contract) matches the fp32 window kernel on the
    dequantized values bitwise."""
    R, K = U.shape
    Cw = cand.shape[1]
    BI, BJ = 8, 128
    Up = _pad_to(_pad_to(U.astype(jnp.float32), BI, 0), 8, 1)
    Vt = jnp.transpose(Vq, (0, 2, 1))                       # (R, K, Cw)
    Vt = _pad_to(_pad_to(_pad_to(Vt, BI, 0), 8, 1), LANE, 2)
    # padded requests dequant with scale 1.0 (their rows are sliced off)
    sc = jnp.pad(scale.astype(jnp.float32).reshape(-1, 1),
                 [(0, (-R) % BI), (0, 0)], constant_values=1.0)
    sp = _pad_to(_pad_to(seen_w.astype(jnp.int8), LANE, 1), BI, 0)
    cp = jnp.pad(cand.astype(jnp.int32),
                 [(0, (-R) % BI), (0, (-Cw) % BJ)],
                 constant_values=-1)
    vals, idx = serve_topk_lib.serve_topk_window_quant_kernel_call(
        Up, Vt, sc, sp, cp, k, block_i=BI, block_j=BJ, interpret=interpret,
    )
    return vals[:R], idx[:R]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def recommend_topk_peruser(U, V, train_mask, k: int, *, interpret: bool | None = None):
    """DMF serving eval: per-user item factors V (I, J, K) — each learner
    scores only his own copy v^i = p^i + q^i. Streams item tiles through a
    running top-k; the (I, J) score matrix never materializes.

    V is transposed to (I, K, J) so the lane dim is J (tiled by 128) and K
    sits on sublanes (padded to the f32 sublane quantum, 8), avoiding a
    16x lane-padding blowup of K."""
    I, K = U.shape
    J = V.shape[1]
    BI, BJ = 128, 128
    Up = _pad_to(_pad_to(U.astype(jnp.float32), BI, 0), 8, 1)
    Vt = jnp.transpose(V.astype(jnp.float32), (0, 2, 1))   # (I, K, J)
    Vt = _pad_to(_pad_to(_pad_to(Vt, BI, 0), 8, 1), BJ, 2)
    # padded users: mask=0 rows score garbage but are sliced off; padded
    # item columns must be masked out so they never enter anyone's top-k
    mp = _pad_to(_pad_to(train_mask.astype(jnp.int8), BJ, 1), BI, 0)
    if mp.shape[1] > J:
        mp = mp.at[:, J:].set(1)
    vals, idx = topk_scores.topk_scores_peruser_kernel_call(
        Up, Vt, mp, k, block_i=BI, block_j=BJ, interpret=interpret,
    )
    return vals[:I], idx[:I]
