"""Mixture-of-Experts FFN (DeepSeek-V2 / Jamba style: shared + routed top-k).

TPU-native expert parallelism (DESIGN.md §5): experts are sharded over the
``model`` mesh axis; token activations enter the block replicated over
``model`` (batch-sharded over ``data``), so device (d, m) already holds all
of data-shard d's tokens *and* expert-shard m's experts — **no all-to-all is
needed**: each device computes the routes that land on its own experts and
the partial outputs are combined by the block's existing tensor-parallel
``psum``. Routes are grouped with a capacity-bounded sort + per-expert
``dynamic_slice`` (static shapes; overflow drops, standard capacity
semantics).

Two code paths with identical math:
* ``moe_ffn_local``   — single-device (smoke tests, and the oracle in tests)
* ``moe_ffn_sharded`` — shard_map over the ``model`` axis (dry-run/cluster)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig


def init_moe(key, cfg: ModelConfig):
    d, E, F = cfg.d_model, cfg.n_routed_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 6)
    s, so = 0.02, 0.02 / math.sqrt(2 * cfg.n_layers)
    params = {
        "router": jax.random.normal(ks[0], (d, E)) * s,
        "wi": jax.random.normal(ks[1], (E, d, F)) * s,
        "wg": jax.random.normal(ks[2], (E, d, F)) * s,
        "wo": jax.random.normal(ks[3], (E, F, d)) * so,
    }
    specs = {
        "router": ("embed_nodiv", None),
        "wi": ("experts", "embed", "expert_ff"),
        "wg": ("experts", "embed", "expert_ff"),
        "wo": ("experts", "expert_ff", "embed"),
    }
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        params |= {
            "shared_wi": jax.random.normal(ks[4], (d, Fs)) * s,
            "shared_wg": jax.random.normal(ks[5], (d, Fs)) * s,
            "shared_wo": jax.random.normal(ks[4], (Fs, d)) * so,
        }
        specs |= {
            "shared_wi": ("embed", "ff"),
            "shared_wg": ("embed", "ff"),
            "shared_wo": ("ff", "embed"),
        }
    return params, specs


def _route(params, x2d: jnp.ndarray, cfg: ModelConfig):
    """Router: softmax-then-topk (DeepSeek-V2). Returns (weights (T,k),
    expert ids (T,k), aux load-balance loss)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e f_e * p_e
    E = cfg.n_routed_experts
    me = probs.mean(0)                                      # mean router prob
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (
        x2d.shape[0] * cfg.moe_top_k
    )
    aux = E * jnp.sum(me * ce)
    return w, idx, aux


def _grouped_expert_ffn(
    params_wi, params_wg, params_wo,   # (E_loc, d, F), (E_loc, F, d)
    x2d: jnp.ndarray,                  # (T, d) tokens (local)
    w: jnp.ndarray,                    # (T, k) combine weights
    idx: jnp.ndarray,                  # (T, k) global expert ids
    first_expert: jnp.ndarray,         # () id of params_wi[0]
    capacity: int,
    dtype,
) -> jnp.ndarray:
    """Capacity-bounded sorted dispatch for the E_loc experts in params.

    Sort all (token, choice) routes by expert id; for each local expert,
    dynamic-slice a capacity-sized window starting at its first route
    (searchsorted), mask entries belonging to other experts (this implements
    both the grouping and capacity dropping), gather→FFN→scatter-add.
    """
    T, k = idx.shape
    E_loc = params_wi.shape[0]
    eid = idx.reshape(-1)
    tok = jnp.repeat(jnp.arange(T), k)
    ww = w.reshape(-1)
    order = jnp.argsort(eid)
    eid_s, tok_s, w_s = eid[order], tok[order], ww[order]
    starts = jnp.searchsorted(eid_s, first_expert + jnp.arange(E_loc))

    def one_expert(y, e_i):
        st = starts[e_i]
        es = jax.lax.dynamic_slice(eid_s, (st,), (capacity,))
        ts = jax.lax.dynamic_slice(tok_s, (st,), (capacity,))
        ws = jax.lax.dynamic_slice(w_s, (st,), (capacity,))
        valid = (es == first_expert + e_i).astype(dtype)
        xs = x2d[ts] * valid[:, None]                      # (C, d)
        h = jnp.einsum("cd,df->cf", xs, params_wi[e_i].astype(dtype))
        g = jnp.einsum("cd,df->cf", xs, params_wg[e_i].astype(dtype))
        o = jnp.einsum("cf,fd->cd", jax.nn.silu(g) * h, params_wo[e_i].astype(dtype))
        y = y.at[ts].add(o * (ws.astype(dtype) * valid)[:, None])
        return y, None

    y0 = jnp.zeros_like(x2d)
    y, _ = jax.lax.scan(one_expert, y0, jnp.arange(E_loc))
    return y


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * cfg.moe_top_k / cfg.n_routed_experts * cfg.capacity_factor))
    # clamp to the total route count (tiny decode batches); at least 1 slot
    return max(1, min(c, n_tokens * cfg.moe_top_k))


def _shared_ffn(params, x, dtype):
    h = jnp.einsum("...d,df->...f", x, params["shared_wi"].astype(dtype))
    g = jnp.einsum("...d,df->...f", x, params["shared_wg"].astype(dtype))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * h, params["shared_wo"].astype(dtype))


def moe_ffn_local(params, x: jnp.ndarray, cfg: ModelConfig, dtype):
    """Single-device path (also the test oracle). x: (B, S, d)."""
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    w, idx, aux = _route(params, x2d, cfg)
    cap = moe_capacity(cfg, x2d.shape[0])
    y = _grouped_expert_ffn(
        params["wi"], params["wg"], params["wo"], x2d, w, idx,
        jnp.zeros((), jnp.int32), cap, dtype,
    )
    if cfg.n_shared_experts:
        y = y + _shared_ffn(params, x2d, dtype)
    return y.reshape(B, S, d), aux


def moe_ffn_sharded(params, x: jnp.ndarray, cfg: ModelConfig, dtype, mesh,
                    weight_stationary: bool = False):
    """Expert-parallel path: shard_map over the full mesh; experts split on
    ``model``; tokens split on batch axes; no token exchange (see module
    docstring). Output psum over ``model``; aux psum-averaged over batch axes.

    ``weight_stationary=True`` (decode-time, §Perf hillclimb): expert weights
    are ADDITIONALLY sharded over the data axis on the hidden (F) dim and
    stay resident; the (tiny) token activations are all-gathered over the
    batch axes instead, and partial outputs psum over the whole mesh. This
    replaces the per-token FSDP *weight* all-gather (GBs) with an
    *activation* all-gather (MBs) — the classic move-activations-not-weights
    inference sharding."""
    B, S, d = x.shape
    E = cfg.n_routed_experts
    axes = mesh.axis_names
    model_ax = "model"
    batch_axes = tuple(a for a in axes if a != model_ax)
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if B % n_batch != 0:   # e.g. batch=1 long-context decode: replicate tokens
        batch_axes = ()
    n_model = mesh.shape[model_ax]
    assert E % n_model == 0, (E, n_model)
    E_loc = E // n_model

    routed_specs = {
        "router": P(),
        "wi": P(model_ax, None, None),
        "wg": P(model_ax, None, None),
        "wo": P(model_ax, None, None),
    }
    # ws: weights 2D-sharded (experts->model, F->all batch axes) and resident;
    # token sharding (x_axes) is independent — batch=1 long-context decode
    # keeps tokens replicated but still wants resident F-sharded weights.
    all_batch = tuple(a for a in axes if a != model_ax)
    ws_axes = all_batch if weight_stationary else ()
    if ws_axes:
        F = cfg.moe_d_ff
        n_ws = 1
        for a in ws_axes:
            n_ws *= mesh.shape[a]
        if F % n_ws != 0:
            ws_axes = ()  # divisibility fallback: plain EP
    x_axes = batch_axes  # () when B not divisible (tokens replicated)
    if ws_axes:
        routed_specs = {
            "router": P(),
            "wi": P(model_ax, None, ws_axes),
            "wg": P(model_ax, None, ws_axes),
            "wo": P(model_ax, ws_axes, None),
        }
    in_specs = (routed_specs, P(x_axes if x_axes else None, None, None))
    out_specs = (P(x_axes if x_axes else None, None, None), P())

    def body(p, xb):
        Bl, Sl, _ = xb.shape
        if ws_axes and x_axes:
            # gather the (small) token batch; weights stay put
            xb = jax.lax.all_gather(xb, x_axes, axis=0, tiled=True)
        Bg = xb.shape[0]
        x2d = xb.reshape(-1, d)
        w, idx, aux = _route(p, x2d, cfg)
        cap = moe_capacity(cfg, x2d.shape[0])
        m_idx = jax.lax.axis_index(model_ax)
        first = (m_idx * E_loc).astype(jnp.int32)
        y = _grouped_expert_ffn(
            p["wi"], p["wg"], p["wo"], x2d, w, idx, first, cap, dtype
        )
        if ws_axes:
            # partial over local F slice and local experts -> full sum
            y = jax.lax.psum(y, (model_ax, *ws_axes))
            if x_axes:  # keep this shard's batch slice
                b_idx = jax.lax.axis_index(x_axes)
                y = jax.lax.dynamic_slice_in_dim(
                    y.reshape(Bg, Sl, d), b_idx * Bl, Bl, axis=0
                ).reshape(Bl * Sl, d)
        else:
            y = jax.lax.psum(y, model_ax)
        if x_axes:
            aux = jax.lax.pmean(aux, x_axes)
        return y.reshape(Bl, Sl, d), aux

    sub = {k: params[k] for k in routed_specs}

    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )(sub, x)
    if cfg.n_shared_experts:
        # shared experts: plain tensor-parallel FFN, outside the shard_map
        y = y + _shared_ffn(params, x, dtype)
    return y, aux
