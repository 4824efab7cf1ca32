"""ShapeDtypeStruct input stand-ins + shardings for every (arch × shape).

``input_specs`` builds the exact abstract inputs each step function lowers
against — weak-type-correct, shardable, zero device allocation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import auto_mesh, batch_axes
from repro.models import ssm as ssm_lib
from repro.models.config import InputShape, ModelConfig


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(auto_mesh(mesh), spec))


def _maybe(ax, size, mesh):
    """Mesh axis (or tuple of axes) if divisible, else None (replicate)."""
    axs = ax if isinstance(ax, tuple) else (ax,)
    n = 1
    for a in axs:
        n *= mesh.shape[a]
    return ax if size % n == 0 else None


def batch_specs(cfg: ModelConfig, shape: InputShape, mesh, batch_over=None):
    """Training/prefill batch: tokens + labels (+ media for VLM).
    ``batch_over`` overrides the batch axes (§Perf dp layout: whole mesh)."""
    ba = batch_over or batch_axes(mesh)
    B, S = shape.global_batch, shape.seq_len
    bax = _maybe(ba if len(ba) > 1 else ba[0], B, mesh)
    tok_shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks else (B, S)
    tok_spec = P(bax, *([None] * (len(tok_shape) - 1)))
    out = {
        "tokens": _sds(tok_shape, jnp.int32, mesh, tok_spec),
        "labels": _sds(tok_shape, jnp.int32, mesh, tok_spec),
    }
    if cfg.n_image_tokens:
        out["media"] = _sds(
            (B, cfg.n_image_tokens, cfg.d_model),
            jnp.dtype(cfg.compute_dtype),
            mesh,
            P(bax, None, None),
        )
    return out


def cache_specs(cfg: ModelConfig, shape: InputShape, mesh):
    """Decode cache stand-ins with the long-context sharding policy:

    * batch dim -> batch axes (when divisible; batch=1 replicates);
    * KV sequence dim -> the *model* axis when batch occupies data
      (decode_32k), or (data, model) when batch=1 (long_500k) — the
      sequence-sharded KV design from DESIGN.md §5/§6;
    * SSM state: heads -> model (O(1) memory, nothing seq-indexed).
    """
    ba = batch_axes(mesh)
    B, S = shape.global_batch, shape.seq_len
    bax = _maybe(ba if len(ba) > 1 else ba[0], B, mesh)
    if bax is None:
        seq_ax = _maybe(tuple([*ba, "model"]), S, mesh)
    else:
        seq_ax = _maybe("model", S, mesh)
    np_ = cfg.n_periods
    dt = jnp.dtype(cfg.compute_dtype)
    cache, specs = {}, {}
    for pos, spec in enumerate(cfg.period):
        if spec.kind == "attn":
            Se = min(S, spec.sliding_window) if spec.sliding_window else S
            seq_ax_e = seq_ax if Se == S else _maybe(
                tuple([*ba, "model"]) if bax is None else "model", Se, mesh)
            if cfg.attn_type == "mla":
                shapes = {
                    "ckv": ((np_, B, Se, cfg.kv_lora_rank), P(None, bax, seq_ax_e, None)),
                    "kr": ((np_, B, Se, cfg.rope_head_dim), P(None, bax, seq_ax_e, None)),
                }
            else:
                kvax = _maybe("model", cfg.n_kv_heads, mesh) if seq_ax_e in (None,) else None
                shapes = {
                    "k": ((np_, B, Se, cfg.n_kv_heads, cfg.head_dim),
                          P(None, bax, seq_ax_e, kvax, None)),
                    "v": ((np_, B, Se, cfg.n_kv_heads, cfg.v_head_dim),
                          P(None, bax, seq_ax_e, kvax, None)),
                }
        elif spec.kind == "cross":
            kvax = _maybe("model", cfg.n_kv_heads, mesh)
            shapes = {
                "mk": ((np_, B, cfg.n_image_tokens, cfg.n_kv_heads, cfg.head_dim),
                       P(None, bax, None, kvax, None)),
                "mv": ((np_, B, cfg.n_image_tokens, cfg.n_kv_heads, cfg.v_head_dim),
                       P(None, bax, None, kvax, None)),
            }
        else:
            cdim = ssm_lib.conv_dim(cfg)
            shapes = {
                "conv": ((np_, B, cfg.ssm_conv_width - 1, cdim),
                         P(None, bax, None, _maybe("model", cdim, mesh))),
                "state": ((np_, B, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state),
                          P(None, bax, _maybe("model", cfg.ssm_n_heads, mesh), None, None)),
            }
        cache[str(pos)] = {
            k: _sds(sh, jnp.float32 if k == "state" else dt, mesh, sp)
            for k, (sh, sp) in shapes.items()
        }
        specs[str(pos)] = {k: sp for k, (sh, sp) in shapes.items()}
    return cache, specs


def decode_specs(cfg: ModelConfig, shape: InputShape, mesh):
    """serve_step inputs: (cache, tokens (B,1), pos ())."""
    ba = batch_axes(mesh)
    B = shape.global_batch
    bax = _maybe(ba if len(ba) > 1 else ba[0], B, mesh)
    cache, cache_pspecs = cache_specs(cfg, shape, mesh)
    tok_shape = (B, 1, cfg.n_codebooks) if cfg.n_codebooks else (B, 1)
    tokens = _sds(tok_shape, jnp.int32, mesh, P(bax, *([None] * (len(tok_shape) - 1))))
    pos = _sds((), jnp.int32, mesh, P())
    return cache, cache_pspecs, tokens, pos
