"""Pallas TPU kernel: serving-time recommendation — masked scores + top-k.

Computes scores = U @ V^T with training items masked to -inf, maintaining a
per-user running top-k across item tiles *inside the kernel*, so the (I, J)
score matrix never hits HBM (the paper's J is small, but a production
recommender has J in the millions — this is the memory-roofline win).

Grid: (I/bi, J/bj) with j innermost; carry (bi, k) value/index buffers in
the output blocks (revisited across j). Top-k per tile via k rounds of
max-extract (k ≤ 16; the paper evaluates k ∈ {5, 10}).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _merge_tile_topk(scores, col, vals, idxs, k):
    """Merge a (bi, bj) tile of candidate scores/indices into the running
    (bi, k) top-k buffers (descending order), via k rounds of extract-max.
    Shared by the shared-V and per-user-V kernels.

    Candidates are ordered by (score descending, item id ascending): each
    round extracts the tile's best candidate under that order and it wins a
    slot on a higher score or, at an equal score, a lower id. The id
    tie-break is what carries an item displaced from slot s past equal
    scores in the later slots; with ``>`` alone it would be dropped in
    favour of a higher id.

    Gathers and scatters are written as masks (Mosaic lowers neither a
    lane gather nor a scatter): the extracted id is a masked min over
    `col`, and each slot write is a `where` on the slot lane. Real ids are
    unique in a tile; the -1 of padded candidates repeats, but only at
    NEG_INF, which never wins a slot."""
    slot_lane = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    for slot in range(k):
        cur_max = jnp.max(scores, axis=-1, keepdims=True)          # (bi,1)
        at_max = scores == cur_max
        cur_idx = jnp.min(jnp.where(at_max, col, jnp.iinfo(jnp.int32).max),
                          axis=-1, keepdims=True)                  # (bi,1)
        consumed = at_max & (col == cur_idx)
        # compare against current slot; if better, shift-insert
        slot_val = vals[:, slot : slot + 1]
        slot_idx = idxs[:, slot : slot + 1]
        better = (cur_max > slot_val) | ((cur_max == slot_val)
                                         & (cur_idx < slot_idx))
        # insert by swapping: new slot value is max(slot, cur); displaced
        # value continues to compete for later slots
        at_slot = slot_lane == slot
        vals = jnp.where(at_slot, jnp.where(better, cur_max, slot_val), vals)
        idxs = jnp.where(at_slot, jnp.where(better, cur_idx, slot_idx), idxs)
        # remove the consumed max from the tile and reinject the displaced
        # candidate so it can fill later slots
        scores = jnp.where(consumed, jnp.where(better, slot_val, cur_max),
                           scores)
        col = jnp.where(consumed, jnp.where(better, slot_idx, cur_idx), col)
    return vals, idxs


def _mask(mask_ref):
    """The int8 mask tile as a predicate laid out like the f32 scores.
    int8 packs four rows per sublane, so comparing it directly gives a
    mask Mosaic cannot broadcast onto an f32 tile ("Sublane broadcast");
    widening to int32 first gives the 32-bit layout."""
    return mask_ref[...].astype(jnp.int32) != 0


def _topk_kernel(u_ref, v_ref, mask_ref, vals_ref, idx_ref, *, k, block_j):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    scores = jnp.dot(u_ref[...], v_ref[...].T, preferred_element_type=jnp.float32)
    scores = jnp.where(_mask(mask_ref), NEG_INF, scores)      # (bi, bj)
    bi, bj = scores.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1) + j * block_j
    vals, idxs = _merge_tile_topk(scores, col, vals_ref[...], idx_ref[...], k)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def _topk_peruser_kernel(u_ref, v_ref, mask_ref, vals_ref, idx_ref, *, k, block_j):
    """DMF serving variant: every user has his *own* item factors (v^i =
    p^i + q^i), so V is laid out (I, K, J) and score is a per-user
    contraction over K (VPU reduce over the sublane dim), not one shared
    matmul. The (I, J) score matrix still never leaves VMEM."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    u = u_ref[...]                                            # (bi, K)
    v = v_ref[...]                                            # (bi, K, bj)
    scores = jnp.sum(u[:, :, None] * v, axis=1)               # (bi, bj)
    scores = jnp.where(_mask(mask_ref), NEG_INF, scores)
    bi, bj = scores.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1) + j * block_j
    vals, idxs = _merge_tile_topk(scores, col, vals_ref[...], idx_ref[...], k)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def topk_scores_kernel_call(U, V, train_mask, k: int, *, block_i: int = 128,
                            block_j: int = 256, interpret: bool | None = None):
    """U: (I, K), V: (J, K), train_mask: (I, J) int8/bool. Returns
    (vals (I, k), idx (I, k)) — per-user top-k unseen items."""
    I, K = U.shape
    J = V.shape[0]
    assert I % block_i == 0 and J % block_j == 0, (I, J, block_i, block_j)
    grid = (I // block_i, J // block_j)
    kern = functools.partial(_topk_kernel, k=k, block_j=block_j)
    vals, idx = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, K), lambda i, j: (j, 0)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((I, k), jnp.float32),
            jax.ShapeDtypeStruct((I, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(U, V, train_mask.astype(jnp.int8))
    return vals, idx


def topk_scores_peruser_kernel_call(U, Vt, train_mask, k: int, *,
                                    block_i: int = 128, block_j: int = 128,
                                    interpret: bool | None = None):
    """U: (I, K), Vt: (I, K, J) per-user item factors (K-major so the lane
    dim is J), train_mask: (I, J). Returns (vals (I, k), idx (I, k))."""
    I, K = U.shape
    J = Vt.shape[2]
    assert Vt.shape[:2] == (I, K), (Vt.shape, U.shape)
    assert I % block_i == 0 and J % block_j == 0, (I, J, block_i, block_j)
    grid = (I // block_i, J // block_j)
    kern = functools.partial(_topk_peruser_kernel, k=k, block_j=block_j)
    vals, idx = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_i, K), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, K, block_j), lambda i, j: (i, 0, j)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_i, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((I, k), jnp.float32),
            jax.ShapeDtypeStruct((I, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(U, Vt, train_mask.astype(jnp.int8))
    return vals, idx
