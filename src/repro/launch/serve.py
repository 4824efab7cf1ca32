"""Serving launcher: prefill and decode steps with the long-context cache
sharding policy (launch/specs.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import auto_mesh
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.sharding import rules


def serve_param_shardings(cfg: ModelConfig, mesh, *, fsdp: bool = True,
                          weight_stationary: bool = False):
    """weight_stationary (§Perf): weights resident — no FSDP dim on the
    embed axis; MoE expert hidden dim sharded over data instead (matches
    moe_ffn_sharded's ws path). Use when the resident footprint fits HBM."""
    mesh = auto_mesh(mesh)
    params_shape, specs = transformer.abstract_params(cfg)
    overrides = dict(rules.SERVE_WS_OVERRIDES) if weight_stationary else None
    pspecs = rules.params_pspecs(specs, params_shape, mesh, fsdp=fsdp,
                                 overrides=overrides)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P)
    )


def make_prefill_step(cfg: ModelConfig, mesh):
    mesh = auto_mesh(mesh)

    def prefill_step(params, batch):
        return transformer.prefill(
            params, batch["tokens"], cfg, media=batch.get("media"), mesh=mesh
        )

    return jax.jit(prefill_step)


def make_decode_step(cfg: ModelConfig, mesh, cache_pspecs):
    mesh = auto_mesh(mesh)
    cache_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), cache_pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )

    def serve_step(params, cache, tokens, pos):
        return transformer.decode_step(params, cache, tokens, pos, cfg, mesh=mesh)

    return jax.jit(
        serve_step,
        donate_argnums=(1,),
        out_shardings=(None, cache_shardings),
    )
