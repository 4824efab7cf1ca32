"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Each case lowers one `kernels.ops` wrapper with ``interpret=False`` for a
v5e chip that is described, not attached (`topologies.get_topology_desc`),
at the widths the system runs: the training step at the default batch, and
the top-k kernels at Foursquare Table 1 scale (6,524 users x 3,197 POIs,
K=10) and at the serving engine's microbatch and candidate-window shapes.
Mosaic then refuses what the interpreter accepts (lane gathers, scatters,
unsupported casts and layouts) here, at no chip time. Nothing runs, so the
cases say nothing about results or speed.

Two more cases compile the whole training epoch (`dmf._epoch_scan`) and
the serve dispatch (`engine._dispatch_rows`) at Table 1 scale with and
without their `jax.named_scope` names: the scopes are op metadata only, so
the optimized programs must match once that metadata is stripped. More
read the compiled epoch itself, plain, under a delay ring, with the
defended `sum` exchange and learner-sharded over the four chips: the walk's
P scatter must stay in place, in the layout the scan carries, with no
per-step relayout of the whole P.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import dmf
from repro.kernels import ops
from repro.robustness.byzantine import DefenseConfig
from repro.serving import engine
from repro.sharding import dmf as sharded_dmf


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _step(u, p, q, r, c):
    return ops.dmf_fused_step(u, p, q, r, c, theta=0.1, alpha=0.1, beta=0.1,
                              gamma=0.01, interpret=False)


def _step_dp(u, p, q, r, c, z):
    return ops.dmf_fused_step_dp(u, p, q, r, c, z, theta=0.1, alpha=0.1,
                                 beta=0.1, gamma=0.01, clip=1.0,
                                 interpret=False)


def _dp_noise(g, rid, seed):
    return ops.dp_clip_noise(g, rid, seed, clip=1.0, noise_std=0.5,
                             interpret=False)


def _peruser(U, V, mask):
    return ops.recommend_topk_peruser(U, V, mask, 10, interpret=False)


def _window(k):
    def f(U, Vw, cand, seen_w):
        return ops.serve_topk_window(U, Vw, cand, seen_w, k, interpret=False)
    return f


def _window_quant(k):
    def f(U, Vq, scale, cand, seen_w):
        return ops.serve_topk_window_quant(U, Vq, scale, cand, seen_w, k,
                                           interpret=False)
    return f


F32, I32, I8, BF16, BOOL = (jnp.float32, jnp.int32, jnp.int8, jnp.bfloat16,
                            jnp.bool_)
B, K = 256, 10                       # DMFConfig.batch_size, dim
I, J = 6524, 3197                    # Foursquare, Table 1


def _windows(R, K, k, dtype=None):
    if dtype is None:
        return _window(k), [((R, K), F32), ((R, 128, K), F32),
                            ((R, 128), I32), ((R, 128), BOOL)]
    return _window_quant(k), [((R, K), F32), ((R, 128, K), dtype), ((R,), F32),
                              ((R, 128), I32), ((R, 128), BOOL)]


CASES = {
    "dmf_fused_step": (_step, [((B, K), F32)] * 3 + [((B,), F32)] * 2),
    "dmf_fused_step_dp": (_step_dp, [((B, K), F32)] * 3 + [((B,), F32)] * 2
                          + [((B, K), F32)]),
    "dp_clip_noise": (_dp_noise, [((B, K), F32), ((B,), I32), ((), I32)]),
    "recommend_topk_peruser": (_peruser, [((I, K), F32), ((I, J, K), F32),
                                          ((I, J), BOOL)]),
    "serve_topk_window_r64": _windows(64, K, 10),
    "serve_topk_window_r128": _windows(128, K, 10),
    "serve_topk_window_quant_int8": _windows(128, 8, 8, I8),
    "serve_topk_window_quant_bf16": _windows(128, 8, 8, BF16),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: no Mosaic kernel in the compiled program")


CFG = dmf.DMFConfig(n_users=I, n_items=J, dim=K, beta=0.1, gamma=0.01,
                    batch_size=B)
NB, S = 110, 21                      # scan steps and walk fan-out, Table 1


def _epoch_scan(one_chip, ring=False, byz=None):
    """The single-device epoch; ``ring`` adds a trivial plan's gates and a
    one-slot delay ring, ``byz`` the gates and the defended exchange."""
    def sds(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds((I, K)), sds((I, J, K)), sds((I, J, K)), sds((I, S), I32),
            sds((I, S)), sds((NB, B), I32), sds((NB, B), I32), sds((NB, B)),
            sds((NB, B)), sds((), I32), CFG)
    if not ring and byz is None:
        return dmf._epoch_scan.lower(*args)
    n = NB * B
    faults = dmf.EpochFaults(
        sds((NB, B)), sds((NB, B)), sds((I,)),
        ring=(sds((1, n, K)), sds((n,), I32), sds((n,), I32), sds((n,)))
        if ring else None)
    return dmf._epoch_scan.lower(*args, faults=faults, byz=byz)


def _epoch_sharded(topo):
    """The learner-sharded epoch over the four described chips."""
    mesh = Mesh(np.asarray(topo.devices), (sharded_dmf.AXIS,))
    D, Bs = len(topo.devices), 96     # shards; routed rows per (batch, shard)

    def sds(shape, dtype=F32, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*spec)))
    rows, batch = sharded_dmf.AXIS, (None, sharded_dmf.AXIS)
    return sharded_dmf._epoch_sharded.lower(
        sds((I, K), F32, rows), sds((I, J, K), F32, rows),
        sds((I, J, K), F32, rows), sds((I, D, S), I32, rows),
        sds((I, D, S), F32, rows), sds((NB, D, Bs), I32, *batch),
        sds((NB, D, Bs), I32, *batch), sds((NB, D, Bs), F32, *batch),
        sds((NB, D, Bs), F32, *batch), sds((NB, D, Bs), F32, *batch),
        sds((NB, D, Bs), I32, *batch), sds((), I32), CFG, mesh)


def _dispatch_rows(one_chip):
    def sds(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return engine._dispatch_rows.lower(
        sds((I, K)), sds((I, J, K)), sds((I, J, K)), sds((I, J), I8),
        sds((117, 384), I32), sds((I,), I32), sds((64,), I32),
        k=10, prune=True)


SCOPED = {
    "epoch_scan": (_epoch_scan,
                   ("dmf.gather_grads", "dmf.local_update", "dmf.p_scatter")),
    "dispatch_rows": (_dispatch_rows, ("serve.window_gather", "serve.topk")),
}


def _strip(hlo: str) -> str:
    """Optimized HLO text without its source tables and op metadata."""
    lines = hlo.splitlines()
    body = next(i for i, ln in enumerate(lines)
                if ln.startswith(("%", "ENTRY")))
    return "\n".join(lines[:1] + [re.sub(r", metadata=\{[^{}]*\}", "", ln)
                                  for ln in lines[body:]])


@pytest.mark.parametrize("name", list(SCOPED))
def test_named_scopes_leave_the_v5e_program_unchanged(one_chip, name,
                                                       monkeypatch):
    lower, scopes = SCOPED[name]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        jax.clear_caches()
        scoped = lower(one_chip).compile().as_text()
        monkeypatch.setattr(jax, "named_scope",
                            lambda _: contextlib.nullcontext())
        jax.clear_caches()
        plain = lower(one_chip).compile().as_text()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", cache)
    for scope in scopes:
        assert scope in scoped, scope
        assert scope not in plain, scope
    assert _strip(scoped) == _strip(plain)


def _computations(hlo: str) -> dict[str, str]:
    """Optimized HLO text split into its computations, by name."""
    comps, name = {}, None
    for ln in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) .*\{$", ln)
        if m:
            name, comps[m.group(1)] = m.group(1), ""
        elif name is not None:
            comps[name] += ln + "\n"
    return comps


def _reachable(comps: dict[str, str], root: str) -> set[str]:
    """``root`` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += re.findall(r"%([\w.\-]+)", comps[c])
    return seen


EPOCHS = {
    "plain": lambda topo, one_chip: _epoch_scan(one_chip),
    "churn_ring": lambda topo, one_chip: _epoch_scan(one_chip, ring=True),
    "byz_sum": lambda topo, one_chip: _epoch_scan(
        one_chip, byz=DefenseConfig(screen=True, aggregation="sum")),
    "sharded4": lambda topo, one_chip: _epoch_sharded(topo),
}


@pytest.mark.parametrize("case", list(EPOCHS))
def test_epoch_scan_scatters_p_in_place_for_v5e(topo, one_chip, case):
    """The P scatter compiles to in-place scatter-adds on P as the scan
    carries it: no op on a flattened I*J (or I*J*K) operand, no while loop
    but the scan, no copy of a whole (I, J, K) factor inside the scan body,
    and less temp memory than the single-scatter form's 4,019,105,280 B.
    On the mesh each chip holds a quarter of the learner rows, so the
    whole factor there is the local (I/4, J, K) block."""
    compiled = EPOCHS[case](topo, one_chip).compile()
    hlo = compiled.as_text()
    rows = I // len(topo.devices) if case == "sharded4" else I
    for flat in {I * J, I * J * K, rows * J, rows * J * K}:
        assert not re.search(rf"\b{flat}\b", hlo), flat
    whiles = re.findall(r"\swhile\(.*?body=%([\w.\-]+)", hlo)
    assert len(whiles) == 1, whiles
    comps = _computations(hlo)
    copy = re.compile(rf"f32\[{rows},{J},{K}\]\{{[^}}]*\}} copy\(")
    body = _reachable(comps, whiles[0])
    assert not [c for c in body if copy.search(comps[c])]
    assert compiled.memory_analysis().temp_size_in_bytes < 4_019_105_280
