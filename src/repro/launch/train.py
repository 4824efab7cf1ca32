"""Training launcher: builds pjit-ed train steps for any --arch.

Two synchronization modes (the paper's axis of comparison):
  * ``allreduce`` — centralized baseline: FSDP+TP sharded params; XLA's
    implicit gradient reduction over the batch axes is the all-reduce the
    paper's DMF removes.
  * ``gossip``    — DMF-adapted: per-learner replicas along
    ``gossip.learner_axis``, local updates, D rounds of ring mixing of the
    *global* partition via collective-permute (core/gossip.py).

Usage (see examples/ and launch/dryrun.py):
    step, state, shardings = make_trainer(cfg, mesh, opt, sync="allreduce")
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import gossip as gossip_lib
from repro.launch.mesh import auto_mesh, batch_axes
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import Optimizer, apply_updates
from repro.sharding import rules


class TrainState(NamedTuple):
    params: Any
    opt_state: Any


def _loss(params, batch, cfg: ModelConfig, mesh):
    return transformer.loss_fn(params, batch, cfg, mesh=mesh)


def make_train_step(cfg: ModelConfig, mesh, opt: Optimizer, *, sync: str = "allreduce",
                    gossip: gossip_lib.GossipConfig | None = None,
                    rules_overrides: dict | None = None):
    """Returns (step_fn, init_fn, param_shardings).

    step_fn(state, batch) -> (state, metrics); already jit-ed with
    in/out shardings bound. init_fn(key) -> sharded TrainState.
    ``rules_overrides`` remaps logical axes (e.g. rules.DP_OVERRIDES for the
    pure-data-parallel §Perf layout).
    """
    mesh = auto_mesh(mesh)
    if sync == "gossip":
        return _make_gossip_step(cfg, mesh, opt, gossip or gossip_lib.GossipConfig())
    return _make_allreduce_step(cfg, mesh, opt, rules_overrides)


def _make_allreduce_step(cfg: ModelConfig, mesh, opt: Optimizer,
                         rules_overrides: dict | None = None):
    params_shape, specs = transformer.abstract_params(cfg)
    pspecs = rules.params_pspecs(specs, params_shape, mesh, overrides=rules_overrides)
    pshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                    is_leaf=lambda x: isinstance(x, P))

    def init_fn(key):
        params, _ = transformer.init_params(cfg, key)
        return TrainState(params, opt.init(params))

    init_jit = jax.jit(
        init_fn,
        out_shardings=TrainState(
            pshard,
            _opt_shardings(opt, params_shape, pshard),
        ),
    )

    def step(state: TrainState, batch):
        loss, grads = jax.value_and_grad(_loss)(state.params, batch, cfg, mesh)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
        return TrainState(params, opt_state), {"loss": loss}

    step_jit = jax.jit(step, donate_argnums=(0,))
    return step_jit, init_jit, pshard


def _opt_shardings(opt: Optimizer, params_shape, pshard):
    """Optimizer-state shardings: moment leaves mirror their parameter's
    sharding (matched by shape); scalars replicate."""
    mesh = jax.tree_util.tree_leaves(pshard)[0].mesh
    repl = NamedSharding(mesh, P())
    opt_shape = jax.eval_shape(opt.init, params_shape)
    by_shape = {}
    for p, s in zip(
        jax.tree_util.tree_leaves(params_shape), jax.tree_util.tree_leaves(pshard)
    ):
        by_shape.setdefault(p.shape, s)
    return jax.tree_util.tree_map(lambda l: by_shape.get(l.shape, repl), opt_shape)


def _make_gossip_step(cfg: ModelConfig, mesh, opt: Optimizer, gcfg: gossip_lib.GossipConfig):
    """Per-learner replicas + ring mixing (DMF protocol)."""
    L = mesh.shape[gcfg.learner_axis]
    params_shape, specs = transformer.abstract_params(cfg)
    # learner axis prepended; FSDP (embed->data) disabled when data is the
    # learner axis (each learner holds a full model-sharded replica)
    st_specs = gossip_lib.stacked_specs(specs, gcfg.learner_axis)
    stacked_shape = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((L, *x.shape), x.dtype), params_shape
    )
    fsdp = gcfg.learner_axis != "data"
    pspecs = rules.params_pspecs(st_specs, stacked_shape, mesh, fsdp=fsdp)
    pshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                    is_leaf=lambda x: isinstance(x, P))

    def init_fn(key):
        params, _ = transformer.init_params(cfg, key)
        stacked = gossip_lib.stack_params(params, L)
        return TrainState(stacked, jax.vmap(opt.init)(stacked))

    init_jit = jax.jit(
        init_fn,
        out_shardings=TrainState(pshard, _opt_shardings(opt, stacked_shape, pshard)),
    )

    def reshape_batch(batch):
        # (B, ...) -> (L, B/L, ...) learner-major
        return jax.tree_util.tree_map(
            lambda x: x.reshape(L, x.shape[0] // L, *x.shape[1:]), batch
        )

    def step(state: TrainState, batch):
        lb = reshape_batch(batch)

        def per_learner(params, b, ostate):
            # NOTE mesh=None: inside vmap the MoE uses the local path; expert
            # sharding still applies through the parameter shardings.
            loss, grads = jax.value_and_grad(_loss)(params, b, cfg, None)
            upd, ostate = opt.update(grads, ostate, params)
            return apply_updates(params, upd), ostate, loss

        params, opt_state, losses = jax.vmap(per_learner)(
            state.params, lb, state.opt_state
        )
        # DMF step: mix the global partition with Ŵ^D (collective-permute)
        params = gossip_lib.mix_global(params, gcfg)
        return TrainState(params, opt_state), {
            "loss": jnp.mean(losses),
            "consensus_err": gossip_lib.consensus_error(params, gcfg),
        }

    step_jit = jax.jit(step, donate_argnums=(0,))
    return step_jit, init_jit, pshard
