"""Pallas TPU kernel: fused per-row L2 clip + Gaussian noise for DP-SGD
gradient messages.

The differential-privacy mechanism on the gradient-exchange hot path
(privacy/mechanism.py): every global-factor gradient message gp leaving a
learner is L2-clipped to norm ≤ C and perturbed with N(0, (σC)²) noise
*before* it is scattered to (or routed across shards toward) any receiver.
Unfused this is three elementwise dispatches over the (B, K) message block
— norm reduction, scale multiply, noise add — each a full VMEM round-trip;
here it is one pass: read gp, reduce the row norm, generate the noise
in-register from a counter-based PRNG, write the noised clipped message.

Counter-based noise (the decentralization requirement): the Gaussian draw
for message-row ``rid``, column ``k`` is a pure function of
``(seed, rid, k)`` — no stateful PRNG, no carried key. The learner-sharded
path routes the same minibatch rows to different shards depending on the
mesh width, so noise keyed by *batch position on a shard* would change
with the shard count; keyed by the row's global stream id it is
shard-count-invariant by construction (tests/test_privacy.py). Stream
layout: counters ``rid*2*KMAX + 2k`` / ``+1`` feed a SplitMix-style 32-bit
hash, two uniforms Box-Muller into one standard normal. ``KMAX = 256``
caps the factor dim (same bound as the other kernels' VMEM-resident K).

Block layout: (Bt, K) tiles of gp in VMEM; rid as a (Bt, 1) int32 column;
seed as a (1, 1) int32 block (replicated to every grid step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

KMAX = 256                 # max factor dim the counter layout supports
_STRIDE = 2 * KMAX         # uint32 counters per message row

# numpy scalars, NOT jnp arrays: jnp constants at module scope become traced
# captures inside the Pallas kernel body (pallas_call rejects them)
_M1 = np.uint32(0x21F0AAAD)    # SplitMix32/lowbias32 mixing constants
_M2 = np.uint32(0x735A2D97)
_GOLDEN = np.uint32(0x9E3779B9)


def _mix32(x):
    """Low-bias 32-bit avalanche hash (uint32 in, uint32 out)."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 15)
    return x


def gauss_counter(seed, rid, n_cols: int):
    """Standard-normal draws as a pure function of (seed, rid, column).

    seed: uint32/int32 scalar; rid: (B, 1) int32 global message-row ids.
    Returns (B, n_cols) f32 ~ N(0, 1): counters 2·(rid·KMAX+k) and +1 are
    hashed to two uniforms, Box-Muller'd to one normal. The SINGLE
    definition of the DP noise stream — the Pallas kernel body and the
    `ref.dp_clip_noise_ref` oracle both call it, so by-spec (not by-luck)
    they perturb with bit-identical noise.
    """
    B = rid.shape[0]
    s = _mix32(jnp.asarray(seed).astype(jnp.uint32))
    col = jax.lax.broadcasted_iota(jnp.uint32, (B, n_cols), 1)
    # the 23 low rid bits index the 512-counter block; the high bits fold
    # into a per-row stream key, so the uint32 counter never wraps — rows
    # 2^23 apart draw from distinct streams, not recycled noise (epochs
    # beyond 8.4M message rows would otherwise reuse draws, and reused
    # noise cancels in update differences)
    rid32 = rid.astype(jnp.uint32)
    s_row = _mix32(s ^ ((rid32 >> np.uint32(23)) * _GOLDEN + np.uint32(1)))
    base = ((rid32 & np.uint32(0x7FFFFF)) * np.uint32(_STRIDE)
            + col * np.uint32(2))
    h1 = _mix32(base ^ s_row)
    h2 = _mix32((base + np.uint32(1)) ^ (s_row * _GOLDEN))
    # 24 high bits -> (0, 1] so log() is finite; [0, 1) for the angle. The
    # values are < 2^25, so the int32 hop (Mosaic has no uint32 -> f32
    # cast) and the f32 conversion are both exact.
    u1 = (((h1 >> np.uint32(8)) + np.uint32(1)).astype(jnp.int32)
          .astype(jnp.float32) * (2.0**-24))
    u2 = (h2 >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) * (2.0**-24)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos((2.0 * jnp.pi) * u2)


def padded_noise(seed, rid, n_real: int, n_cols: int):
    """(B, n_cols) noise block with draws only for the ``n_real`` live
    columns, zero on the K-padding — the padded lanes are sliced off by the
    wrappers anyway, and the transcendentals (log/cos) dominate the
    mechanism's cost, so generating 128-lane noise for a K=10 factor would
    be ~13x wasted work per batch (felt acutely in interpret mode)."""
    z = gauss_counter(seed, rid, n_real)
    if n_cols > n_real:
        z = jnp.pad(z, ((0, 0), (0, n_cols - n_real)))
    return z


def _dp_clip_noise_kernel(g_ref, rid_ref, seed_ref, out_ref,
                          *, clip, noise_std, n_real, n_cols):
    g = g_ref[...]                                       # (Bt, K)
    nrm = jnp.sqrt(jnp.sum(g * g, axis=-1, keepdims=True))
    scale = jnp.minimum(1.0, clip / nrm)                 # inf/0 -> 1 (no-op)
    out = g * scale
    if noise_std > 0.0:
        z = padded_noise(seed_ref[0, 0], rid_ref[...], n_real, n_cols)
        out = out + noise_std * z
    out_ref[...] = out


def dp_clip_noise_kernel_call(g, rid, seed, *, clip: float, noise_std: float,
                              n_real: int | None = None, block_b: int = 256,
                              interpret: bool | None = None):
    """g: (B, K) f32 messages (K lane-aligned by the wrapper); rid: (B,)
    int32 global row ids; seed: (1, 1) int32; ``n_real``: live columns
    (noise is only generated for those — the rest is K-padding the wrapper
    slices off). Padded K columns must be zero (they then contribute
    nothing to the row norm).
    """
    B, K = g.shape
    assert B % block_b == 0, (B, block_b)
    assert K <= KMAX, (K, KMAX)
    n_real = K if n_real is None else n_real
    rid2 = rid.reshape(B, 1)
    grid = (B // block_b,)
    bspec_mat = pl.BlockSpec((block_b, K), lambda i: (i, 0))
    bspec_col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    bspec_seed = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kern = functools.partial(
        _dp_clip_noise_kernel, clip=clip, noise_std=noise_std, n_real=n_real,
        n_cols=K)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[bspec_mat, bspec_col, bspec_seed],
        out_specs=bspec_mat,
        out_shape=jax.ShapeDtypeStruct((B, K), g.dtype),
        interpret=resolve_interpret(interpret),
    )(g, rid2, seed)
