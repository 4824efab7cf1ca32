"""Pallas TPU kernel: geo-pruned batched serving — candidate gather +
per-user scores + masked running top-k, fused.

A microbatch of R requests arrives with each learner's own factors
(u_i (K,), v^i = p^i + q^i (J, K) — the decentralized per-user item view)
and a per-request candidate row from the city bucket index
(`serving/candidates.py`, (R, Cw) global item ids, -1 padded). The kernel
fuses, per (request, candidate) tile in one VMEM pass:

    gather v^i at the candidate ids  →  scores u_i · v^i_cand
    →  seen/pad masking  →  merge into the running per-request top-k.

Only O(Cw·K) *compute* is done per request instead of O(J·K): the grid's
inner axis tiles the *candidate* dim, not the item dim — that is the
geo-pruning (paper Fig. 2: check-ins concentrate in the home city). The
gather source (the request's item slab) is still staged whole — see
`ops.serve_topk` for the HBM/DMA shape of a DMA-fed design.

The candidate gather is a per-row `take_along_axis` over the request's own
item slab held in VMEM; the output index buffer carries global item ids
directly (no position→id remap pass afterwards). Unfilled slots (fewer
unseen candidates than k, incl. all-seen users) stay at (NEG_INF, -1).

Layout mirrors `topk_scores._topk_peruser_kernel`: V comes in as (R, K, J)
so the lane dim is J and K sits on sublanes. A compiled design would DMA
the per-request slab from HBM per candidate window instead of staging it
whole — the compute and the top-k carry are identical.

Two kernel families live here:

* ``_serve_topk_kernel`` — the original whole-slab kernel: every request
  hands its FULL item slab (R, K, J) to the kernel and the candidate gather
  happens inside. Kept as the staging reference; physically impossible at
  J=100k (a 64-request microbatch would stage 64·J·K floats).
* ``_serve_topk_window_kernel`` / ``_serve_topk_window_quant_kernel`` — the
  tiled million-scale path: the candidate windows (R, K, Cw) are gathered
  OUTSIDE the kernel from the HBM-resident factor store (`serving/store.py`
  slab, or row-gathers of V/P/Q in the engine dispatches), and the grid's
  inner axis streams (block_i, K, block_j) window tiles through VMEM — the
  staged working set is O(R·Cw·K) regardless of J. Scores, masking and the
  `_merge_tile_topk` carry are byte-for-byte the same computation as the
  whole-slab kernel, so the two are bitwise identical on shared inputs.
  The quant variant takes int8 codes (+ a per-request f32 dequant scale) or
  bf16 factors and dequantizes in-VMEM before the identical score/merge.

Tie contract (load-bearing for the exact-id guarantee): `_merge_tile_topk`
ranks by (score descending, item id ascending), and candidate rows are in
ascending item-id order, so equal scores resolve to the lowest item id —
the same tie-break as `jax.lax.top_k` on dense scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

from repro.kernels.topk_scores import NEG_INF, _merge_tile_topk


def _serve_topk_kernel(u_ref, v_ref, seen_ref, cand_ref, vals_ref, idx_ref, *, k):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    u = u_ref[...]                                            # (bi, K)
    cand = cand_ref[...]                                      # (bi, bj) ids
    safe = jnp.maximum(cand, 0)                               # pad-safe gather
    v = v_ref[...]                                            # (bi, K, J)
    vc = jnp.take_along_axis(v, safe[:, None, :], axis=2)     # (bi, K, bj)
    scores = jnp.sum(u[:, :, None] * vc, axis=1)              # (bi, bj)
    seen = jnp.take_along_axis(seen_ref[...], safe, axis=1)   # (bi, bj)
    scores = jnp.where((cand < 0) | (seen != 0), NEG_INF, scores)
    vals, idxs = _merge_tile_topk(scores, cand, vals_ref[...], idx_ref[...], k)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def serve_topk_kernel_call(U, Vt, seen, cand, k: int, *, block_i: int = 8,
                           block_j: int = 128, interpret: bool | None = None):
    """U: (R, K), Vt: (R, K, J) per-request item factors, seen: (R, J) int8,
    cand: (R, Cw) int32 global item ids (-1 = padded slot). Returns
    (vals (R, k), idx (R, k)) with idx holding global item ids, -1 where
    fewer than k unseen candidates exist."""
    R, K = U.shape
    J = Vt.shape[2]
    Cw = cand.shape[1]
    assert Vt.shape[:2] == (R, K), (Vt.shape, U.shape)
    assert seen.shape == (R, J), (seen.shape, R, J)
    assert R % block_i == 0 and Cw % block_j == 0, (R, Cw, block_i, block_j)
    assert k <= block_j, (k, block_j)
    grid = (R // block_i, Cw // block_j)
    kern = functools.partial(_serve_topk_kernel, k=k)
    vals, idx = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, K, J), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_i, J), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, k), jnp.float32),
            jax.ShapeDtypeStruct((R, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(U, Vt, seen.astype(jnp.int8), cand)
    return vals, idx


def _serve_topk_window_kernel(u_ref, v_ref, seen_ref, cand_ref, vals_ref,
                              idx_ref, *, k):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    u = u_ref[...]                                            # (bi, K)
    cand = cand_ref[...]                                      # (bi, bj) ids
    vc = v_ref[...]                                           # (bi, K, bj)
    scores = jnp.sum(u[:, :, None] * vc, axis=1)              # (bi, bj)
    scores = jnp.where((cand < 0) | (seen_ref[...] != 0), NEG_INF, scores)
    vals, idxs = _merge_tile_topk(scores, cand, vals_ref[...], idx_ref[...], k)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def serve_topk_window_kernel_call(U, Vw, seen_w, cand, k: int, *,
                                  block_i: int = 8, block_j: int = 128,
                                  interpret: bool | None = None):
    """Tiled serving over pre-gathered candidate windows. U: (R, K);
    Vw: (R, K, Cw) the requests' candidate-window item factors (K-major, the
    same layout the slab kernel produces internally from its gather);
    seen_w: (R, Cw) int8 seen bits aligned to `cand`; cand: (R, Cw) int32
    global item ids, -1 padded. The grid's inner axis streams (bi, K, bj)
    window tiles — per-step VMEM is independent of J, so the factor source
    can stay HBM-resident at million-user scale. Bitwise identical to
    `serve_topk_kernel_call` when Vw/seen_w hold the slab-gathered values:
    same block sizes, same K-major contraction, same `_merge_tile_topk`
    carry, same tie contract."""
    R, K = U.shape
    Cw = cand.shape[1]
    assert Vw.shape == (R, K, Cw), (Vw.shape, (R, K, Cw))
    assert seen_w.shape == (R, Cw), (seen_w.shape, (R, Cw))
    assert R % block_i == 0 and Cw % block_j == 0, (R, Cw, block_i, block_j)
    assert k <= block_j, (k, block_j)
    grid = (R // block_i, Cw // block_j)
    kern = functools.partial(_serve_topk_window_kernel, k=k)
    vals, idx = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, K, block_j), lambda i, j: (i, 0, j)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, k), jnp.float32),
            jax.ShapeDtypeStruct((R, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(U, Vw, seen_w.astype(jnp.int8), cand)
    return vals, idx


def _serve_topk_window_quant_kernel(u_ref, v_ref, scale_ref, seen_ref,
                                    cand_ref, vals_ref, idx_ref, *, k):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    u = u_ref[...]                                            # (bi, K)
    cand = cand_ref[...]                                      # (bi, bj) ids
    scale = scale_ref[...]                                    # (bi, 1)
    # in-VMEM dequant: int8 codes × per-request scale (bf16 rides the same
    # path with scale=1 — the upcast IS the dequant), then the identical
    # K-major contraction + merge as the fp32 window kernel
    vc = v_ref[...].astype(jnp.float32) * scale[:, :, None]   # (bi, K, bj)
    scores = jnp.sum(u[:, :, None] * vc, axis=1)              # (bi, bj)
    scores = jnp.where((cand < 0) | (seen_ref[...] != 0), NEG_INF, scores)
    vals, idxs = _merge_tile_topk(scores, cand, vals_ref[...], idx_ref[...], k)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def serve_topk_window_quant_kernel_call(U, Vq, scale, seen_w, cand, k: int, *,
                                        block_i: int = 8, block_j: int = 128,
                                        interpret: bool | None = None):
    """Quantized tiled serving: `serve_topk_window_kernel_call` with the
    candidate windows carried as int8 codes (plus a per-request f32 dequant
    scale, (R, 1)) or bf16 factors (scale = 1.0). Dequantization happens
    per (bi, K, bj) tile in VMEM — HBM traffic shrinks by the quant ratio
    (4x for int8, 2x for bf16). On real TPU int8 windows obey the (32, 128)
    tile minimum; interpret mode does not enforce it. Score error is
    bounded per request by ||u||₁ · scale/2 (int8, round-to-nearest codes)
    resp. Σ_k |u_k·v_k|·2⁻⁸ (bf16) — measured in BENCH_serving."""
    R, K = U.shape
    Cw = cand.shape[1]
    assert Vq.shape == (R, K, Cw), (Vq.shape, (R, K, Cw))
    assert scale.shape == (R, 1), scale.shape
    assert seen_w.shape == (R, Cw), (seen_w.shape, (R, Cw))
    assert R % block_i == 0 and Cw % block_j == 0, (R, Cw, block_i, block_j)
    assert k <= block_j, (k, block_j)
    grid = (R // block_i, Cw // block_j)
    kern = functools.partial(_serve_topk_window_quant_kernel, k=k)
    vals, idx = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, K, block_j), lambda i, j: (i, 0, j)),
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, k), jnp.float32),
            jax.ShapeDtypeStruct((R, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(U, Vq, scale, seen_w.astype(jnp.int8), cand)
    return vals, idx
