"""Pallas TPU kernel: random-walk propagation mixing  Y = M @ X.

Alg. 1 lines 13-15 vectorized: M (I, I) is the walk-propagation matrix
(graph.walk_propagation_matrix), X (I, F) the flattened per-learner global
state (or a batch of gradient messages). This is the MXU workload of the
paper's communication step — a classic tiled matmul with an accumulator
tile resident in VMEM and a K-loop over I.

Grid: (I/bm, F/bn, I/bk); the (bm, bn) f32 accumulator lives in the output
block (revisited across the k dimension — Pallas guarantees grid-minor
revisiting order, k is the innermost axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _mix_kernel(m_ref, x_ref, y_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[...] += jnp.dot(
        m_ref[...], x_ref[...], preferred_element_type=jnp.float32
    )


def gossip_mix_kernel_call(M, X, *, block_m: int = 128, block_n: int = 128,
                           block_k: int = 128, interpret: bool | None = None):
    """M: (I, I) f32, X: (I, F) f32 -> (I, F). Dims must be multiples of the
    MXU-aligned block sizes (the ops.py wrapper pads)."""
    I, I2 = M.shape
    _, F = X.shape
    assert I == I2 and I % block_m == 0 and I % block_k == 0 and F % block_n == 0
    grid = (I // block_m, F // block_n, I // block_k)
    return pl.pallas_call(
        _mix_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((I, F), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(M, X)
