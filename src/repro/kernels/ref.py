"""Pure-jnp oracles for every Pallas kernel, and the top-k contract the
serving kernels are held to against them (`assert_topk_matches`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dmf_grads_ref(u, p, q, r, conf, alpha, beta, gamma):
    """Fused DMF per-rating gradients (paper Eqs. 9-11), confidence-weighted.

    u, p, q: (B, K); r, conf: (B,). Returns (gu, gp, gq) each (B, K).
    """
    v = p + q
    err = conf * (r - jnp.sum(u * v, axis=-1))
    gu = -err[:, None] * v + alpha * u
    gp = -err[:, None] * u + beta * p
    gq = -err[:, None] * u + gamma * q
    return gu, gp, gq


def dmf_fused_step_ref(u, p, q, r, conf, theta, alpha, beta, gamma):
    """Fused Alg. 1 step oracle: (du, gp, dq, loss) = lr-scaled deltas for
    the sender's u/q, raw global-factor gradient message, batch loss."""
    gu, gp, gq = dmf_grads_ref(u, p, q, r, conf, alpha, beta, gamma)
    raw = r - jnp.sum(u * (p + q), axis=-1)
    loss = 0.5 * jnp.sum(conf * raw * raw)
    return -theta * gu, gp, -theta * gq, loss


def peruser_scores(U, V, train_mask):
    """(I, J) per-user scores u_i . v^i_j, -inf where ``train_mask``. U:
    (I, K), V: (I, J, K). K-major elementwise contraction (see
    serve_topk_ref): it also keeps the oracle in float32 on a TPU, where an
    einsum would take the MXU's reduced default precision."""
    scores = jnp.sum(U[:, :, None] * jnp.transpose(V, (0, 2, 1)), axis=1)
    return jnp.where(train_mask, -jnp.inf, scores)


def topk_scores_peruser_ref(U, V, train_mask, k):
    """Per-user-factor serving oracle. U: (I, K), V: (I, J, K)."""
    return jax.lax.top_k(peruser_scores(U, V, train_mask), k)


# single source of the dead-slot sentinel: the oracle must use the exact
# value the kernels fill unmerged slots with, or bitwise-equality breaks
from repro.kernels.topk_scores import NEG_INF  # noqa: E402


def masked_topk_finalize(vals, idx):
    """Normalize a dense `lax.top_k` result to the streaming-kernel contract:
    slots whose score is masked-out (≤ NEG_INF, incl. -inf) become
    (NEG_INF, -1) — `top_k` otherwise reports arbitrary indices there."""
    dead = vals <= NEG_INF
    return jnp.where(dead, NEG_INF, vals), jnp.where(dead, -1, idx)


def serve_topk_ref(U, V, cand, seen, k):
    """Geo-pruned serving oracle: dense per-request scores, masked to the
    candidate bucket and the seen-filter, then `lax.top_k`.

    U: (R, K); V: (R, J, K); cand: (R, Cw) int32 item ids (-1 pad);
    seen: (R, J) bool/int8. Returns (vals (R, k), idx (R, k)) with -1/NEG_INF
    in unfilled slots — the exact-equality target for `ops.serve_topk`.
    """
    R, J, _ = V.shape
    # K-major contraction (not einsum): reduction grouping over K is then
    # invariant to sublane padding, so the Pallas kernel matches *bitwise*
    # (einsum picks a different association, off by ~1 ulp).
    scores = jnp.sum(U[:, :, None] * jnp.transpose(V, (0, 2, 1)), axis=1)
    elig = jnp.zeros((R, J), bool).at[
        jnp.arange(R)[:, None], jnp.maximum(cand, 0)
    ].max(cand >= 0)
    scores = jnp.where(elig & (seen == 0), scores, NEG_INF)
    vals, idx = jax.lax.top_k(scores, k)
    return masked_topk_finalize(vals, idx)


def window_scores(U, Vw, cand, seen_w):
    """(R, Cw) candidate-window scores, NEG_INF on padded or seen slots."""
    # K-major contraction (not einsum) — see serve_topk_ref
    scores = jnp.sum(U[:, :, None] * jnp.transpose(Vw, (0, 2, 1)), axis=1)
    return jnp.where((cand < 0) | (seen_w != 0), NEG_INF, scores)


def serve_topk_window_ref(U, Vw, cand, seen_w, k):
    """Tiled-serving oracle over pre-gathered candidate windows: window
    scores, pad/seen masking, dense `lax.top_k` over window positions, then
    position→item-id remap — the exact-equality target for
    `ops.serve_topk_window` (and, on dequantized windows, for
    `ops.serve_topk_window_quant`).

    U: (R, K); Vw: (R, Cw, K); cand: (R, Cw) int32 item ids (-1 pad);
    seen_w: (R, Cw) bool/int8 aligned to cand. Candidate rows are ascending
    in item id (index contract), so `top_k`'s lowest-position tie-break is
    the same lowest-item-id tie-break the streaming kernel implements.
    """
    vals, pos = jax.lax.top_k(window_scores(U, Vw, cand, seen_w), k)
    idx = jnp.take_along_axis(jnp.maximum(cand, 0), pos, axis=1)
    return masked_topk_finalize(vals, idx)


# Serving contract: the streaming top-k kernels return the oracle's item ids
# exactly and its scores to within MAX_ULP units in the last place of the
# row's dot-product magnitude max_j sum_k |u_k v_jk|. The kernel and the
# oracle contract K in different association orders (and XLA picks its own
# per backend and version), so bitwise equality is not a property a
# backend keeps. The rounding error of a K-term sum scales with its terms,
# not with the (possibly cancelled) result: counted against each score
# itself, a 1e-8 difference on a score near zero is tens of ULP.
MAX_ULP = 4


def assert_topk_matches(vals, idx, v_ref, i_ref, U, V, *, ref_scores=None,
                        max_ulp: int = MAX_ULP) -> dict:
    """Assert the serving contract of a kernel top-k ``(vals, idx)`` against
    its oracle ``(v_ref, i_ref)``, all (n, k), for factors U (n, K) and
    item rows V (n, m, K): every score within ``max_ulp`` ULP of the row's
    magnitude, dead slots (NEG_INF, -1) where the oracle has them, and
    item ids equal.

    With ``ref_scores`` — the oracle's masked (n, J) score rows, indexed by
    item id — an id may differ from the oracle's only at a near-tie: the
    kernel's item must score within the same bound of the oracle's value
    in that slot (two items that close may swap under another summation
    order). Returns ``{"max_ulp", "id_mismatches"}``, the largest score
    difference in row ULPs and the number of near-tie swaps."""
    vals, idx, v_ref, i_ref = (np.asarray(a) for a in (vals, idx, v_ref,
                                                        i_ref))
    assert vals.shape == v_ref.shape and idx.shape == i_ref.shape, (
        vals.shape, v_ref.shape, idx.shape, i_ref.shape)
    mag = np.einsum("nk,nmk->nm", np.abs(np.asarray(U, np.float64)),
                    np.abs(np.asarray(V, np.float64))).max(axis=1, initial=0)
    unit = np.spacing(mag.astype(np.float32)).astype(np.float64)[:, None]
    err = np.abs(vals.astype(np.float64) - v_ref.astype(np.float64)) / unit
    assert err.max(initial=0.0) <= max_ulp, (
        f"scores differ by {err.max():.3g} row ULP > {max_ulp}")
    diff = idx != i_ref
    if ref_scores is None or not diff.any():
        np.testing.assert_array_equal(idx, i_ref)
    else:
        rows, slots = np.nonzero(diff)
        got = idx[rows, slots]
        assert (got >= 0).all() and (i_ref[rows, slots] >= 0).all(), (
            "a filled slot differs from an empty one")
        tie = np.abs(np.asarray(ref_scores, np.float64)[rows, got]
                     - v_ref[rows, slots]) / unit[rows, 0]
        assert tie.max() <= max_ulp, (
            f"an id differs from the oracle's without a near-tie "
            f"({tie.max():.3g} row ULP apart)")
        for r in np.unique(rows):
            ids = idx[r][idx[r] >= 0]
            assert len(set(ids.tolist())) == len(ids), (
                f"row {r}: an item is recommended twice")
    return {"max_ulp": float(err.max(initial=0.0)),
            "id_mismatches": int(diff.sum())}


def dp_clip_noise_ref(g, rid, seed, clip, noise_std):
    """DP gradient-message mechanism oracle: per-row L2 clip to ``clip``
    then additive N(0, noise_std²) noise.

    g: (B, K) f32; rid: (B,) int32 global message-row ids; seed: int32.
    The noise stream itself is spec'd as `dp_noise.gauss_counter` — a pure
    function of (seed, rid, column) — so the oracle draws the *identical*
    perturbation the fused kernel applies (the mechanism is deterministic
    by design; only the clip-norm reduction is re-derived independently).
    """
    from repro.kernels.dp_noise import gauss_counter

    B, K = g.shape
    nrm = jnp.sqrt(jnp.sum(g * g, axis=-1, keepdims=True))
    out = g * jnp.minimum(1.0, clip / nrm)
    if noise_std > 0.0:
        out = out + noise_std * gauss_counter(seed, rid.reshape(B, 1), K)
    return out


def gossip_mix_ref(M, X):
    """Propagation mixing: (I, I) walk matrix times flattened learner state
    (I, F) — Alg. 1 line 15 vectorized over receivers."""
    return jnp.einsum("ij,jf->if", M, X)


def topk_scores_ref(U, V, train_mask, k):
    """Serving: masked preference scores + per-user top-k.

    U: (I, K), V: (J, K), train_mask: (I, J) bool. Returns (vals, idx)."""
    scores = U @ V.T
    scores = jnp.where(train_mask, -jnp.inf, scores)
    return jax.lax.top_k(scores, k)
