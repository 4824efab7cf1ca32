"""Pallas TPU kernel: fused DMF per-rating gradients (paper Eqs. 9-11).

The paper's hot inner loop — for a minibatch of gathered factors, compute
the confidence-weighted residual and all three gradients in one pass. On
TPU this is a VPU-bound fusion: one read of (u, p, q), residual reduction,
three FMA writes — vs. 4 separate HBM round-trips in the naive op-by-op
form. Batch dim is tiled over a grid; K stays resident in VMEM (K ≤ 256
for any MF workload — the paper uses K ∈ {5, 10, 15}, padded to the
128-lane boundary by the wrapper).

Block layout: (Bt, K) tiles of u/p/q in VMEM; r/conf as (Bt, 1) columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _dmf_grads_kernel(u_ref, p_ref, q_ref, r_ref, c_ref,
                      gu_ref, gp_ref, gq_ref, *, alpha, beta, gamma):
    u = u_ref[...]
    p = p_ref[...]
    q = q_ref[...]
    r = r_ref[...]          # (Bt, 1)
    c = c_ref[...]          # (Bt, 1)
    v = p + q
    pred = jnp.sum(u * v, axis=-1, keepdims=True)       # (Bt, 1)
    err = c * (r - pred)                                # (Bt, 1)
    gu_ref[...] = -err * v + alpha * u
    gp_ref[...] = -err * u + beta * p
    gq_ref[...] = -err * u + gamma * q


def dmf_grads_kernel_call(u, p, q, r, conf, *, alpha, beta, gamma,
                          block_b: int = 256, interpret: bool | None = None):
    """u/p/q: (B, K) f32; r/conf: (B,). K should be lane-aligned (wrapper
    pads). Returns (gu, gp, gq)."""
    B, K = u.shape
    assert B % block_b == 0, (B, block_b)
    r2 = r.reshape(B, 1)
    c2 = conf.reshape(B, 1)
    grid = (B // block_b,)
    bspec_mat = pl.BlockSpec((block_b, K), lambda i: (i, 0))
    bspec_col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((B, K), u.dtype)] * 3
    kern = functools.partial(_dmf_grads_kernel, alpha=alpha, beta=beta, gamma=gamma)
    gu, gp, gq = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[bspec_mat, bspec_mat, bspec_mat, bspec_col, bspec_col],
        out_specs=[bspec_mat, bspec_mat, bspec_mat],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(u, p, q, r2, c2)
    return gu, gp, gq


def _dmf_fused_step_kernel(u_ref, p_ref, q_ref, r_ref, c_ref,
                           du_ref, gp_ref, dq_ref, loss_ref,
                           *, theta, alpha, beta, gamma):
    """Fused training step body: residual → Eqs. 9-11 grads → lr-scaled
    deltas for the sender's own state, plus the raw global-factor gradient
    gp (the *message* — receivers scale it by their own walk weight) and
    the batch loss, all in one VMEM pass. The loss block is revisited by
    every grid step and accumulated in place (grid is sequential on TPU)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        loss_ref[...] = jnp.zeros_like(loss_ref)

    u = u_ref[...]
    p = p_ref[...]
    q = q_ref[...]
    r = r_ref[...]          # (Bt, 1)
    c = c_ref[...]          # (Bt, 1)
    v = p + q
    raw = r - jnp.sum(u * v, axis=-1, keepdims=True)    # (Bt, 1)
    err = c * raw
    gu = -err * v + alpha * u
    gp = -err * u + beta * p
    gq = -err * u + gamma * q
    du_ref[...] = -theta * gu
    gp_ref[...] = gp
    dq_ref[...] = -theta * gq
    loss_ref[...] += 0.5 * jnp.sum(c * raw * raw)


def _dmf_fused_step_dp_kernel(u_ref, p_ref, q_ref, r_ref, c_ref, z_ref,
                              du_ref, gp_ref, dq_ref, loss_ref,
                              *, theta, alpha, beta, gamma, clip):
    """The fused step WITH the DP mechanism folded in: Eqs. 9-11, lr-scaled
    deltas, batch loss, AND the per-row L2 clip + noise add on the outgoing
    gp message — still ONE VMEM pass, so the DP path keeps the un-noised
    path's one-kernel-per-minibatch dispatch count. ``z`` is the
    pre-scaled noise block for this batch: drawn from the counter-keyed
    stream (`dp_noise.gauss_counter`, keyed by global stream row id) in ONE
    vectorized epoch-level pass and streamed in per batch — generating
    in-kernel per batch pays the transcendental dispatch cost 70x per
    epoch for the same bits (the standalone `dp_noise` kernel keeps the
    in-kernel generation as the self-contained mechanism op)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        loss_ref[...] = jnp.zeros_like(loss_ref)

    u = u_ref[...]
    p = p_ref[...]
    q = q_ref[...]
    r = r_ref[...]          # (Bt, 1)
    c = c_ref[...]          # (Bt, 1)
    v = p + q
    raw = r - jnp.sum(u * v, axis=-1, keepdims=True)    # (Bt, 1)
    err = c * raw
    gu = -err * v + alpha * u
    gp = -err * u + beta * p
    gq = -err * u + gamma * q
    nrm = jnp.sqrt(jnp.sum(gp * gp, axis=-1, keepdims=True))
    gp = gp * jnp.minimum(1.0, clip / nrm)              # inf/0 -> 1 (no-op)
    du_ref[...] = -theta * gu
    gp_ref[...] = gp + z_ref[...]
    dq_ref[...] = -theta * gq
    loss_ref[...] += 0.5 * jnp.sum(c * raw * raw)


def dmf_fused_step_dp_kernel_call(u, p, q, r, conf, z, *, theta, alpha, beta,
                                  gamma, clip, block_b: int = 256,
                                  interpret: bool | None = None):
    """DP variant of `dmf_fused_step_kernel_call`: extra input z (B, K) —
    the pre-scaled σC-Gaussian noise for this batch's messages (zero on
    padded rows/columns). Returns (du, g̃p, dq, loss) with g̃p the
    clipped+noised message."""
    B, K = u.shape
    assert B % block_b == 0, (B, block_b)
    r2 = r.reshape(B, 1)
    c2 = conf.reshape(B, 1)
    grid = (B // block_b,)
    bspec_mat = pl.BlockSpec((block_b, K), lambda i: (i, 0))
    bspec_col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    bspec_loss = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kern = functools.partial(
        _dmf_fused_step_dp_kernel, theta=theta, alpha=alpha, beta=beta,
        gamma=gamma, clip=clip)
    du, gp, dq, loss = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[bspec_mat, bspec_mat, bspec_mat, bspec_col, bspec_col,
                  bspec_mat],
        out_specs=[bspec_mat, bspec_mat, bspec_mat, bspec_loss],
        out_shape=[
            jax.ShapeDtypeStruct((B, K), u.dtype),
            jax.ShapeDtypeStruct((B, K), u.dtype),
            jax.ShapeDtypeStruct((B, K), u.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(u, p, q, r2, c2, z)
    return du, gp, dq, loss


def dmf_fused_step_kernel_call(u, p, q, r, conf, *, theta, alpha, beta, gamma,
                               block_b: int = 256, interpret: bool | None = None):
    """u/p/q: (B, K) f32 (K lane-aligned by the wrapper); r/conf: (B,).
    Returns (du, gp, dq, loss): the -θ·grad deltas for u and q, the raw
    propagation gradient for p, and the summed batch loss (1, 1)."""
    B, K = u.shape
    assert B % block_b == 0, (B, block_b)
    r2 = r.reshape(B, 1)
    c2 = conf.reshape(B, 1)
    grid = (B // block_b,)
    bspec_mat = pl.BlockSpec((block_b, K), lambda i: (i, 0))
    bspec_col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    bspec_loss = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kern = functools.partial(
        _dmf_fused_step_kernel, theta=theta, alpha=alpha, beta=beta, gamma=gamma
    )
    du, gp, dq, loss = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[bspec_mat, bspec_mat, bspec_mat, bspec_col, bspec_col],
        out_specs=[bspec_mat, bspec_mat, bspec_mat, bspec_loss],
        out_shape=[
            jax.ShapeDtypeStruct((B, K), u.dtype),
            jax.ShapeDtypeStruct((B, K), u.dtype),
            jax.ShapeDtypeStruct((B, K), u.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(u, p, q, r2, c2)
    return du, gp, dq, loss
