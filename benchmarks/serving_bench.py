"""Serving-path benchmark: sustained throughput + latency percentiles for
the three request paths over one trained model —

  * ``loop_per_request`` — the seed behavior: one interpret-mode kernel
    dispatch per request (`ops.recommend_topk` with a single-user batch);
  * ``batched_dense``    — `ServingEngine(prune=False)`: microbatched,
    full-J streaming top-k per request;
  * ``batched_pruned``   — `ServingEngine(prune=True)`: microbatched +
    city-bucket candidate pruning through the fused serve kernel.

Writes ``BENCH_serving.json`` (repo root + benchmarks/results/, same
convention as BENCH_dmf_train). Required: batched_pruned ≥ 10x the
per-request loop in requests/sec at foursquare_like(reduced=True) scale.
Also reports how often the pruned top-k agrees with the dense full-J
top-k (Fig. 2 says almost always) and the per-microbatch latency
percentiles of both engine paths.

The ``sharded`` section measures the learner-sharded SPMD engine
(`ServingConfig.n_shards`) by shard count — each dispatch serves
microbatch×n_shards requests, recommendations bit-identical to the
single-shard engine. Needs host devices provisioned before jax starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m benchmarks.serving_bench
    # or: PYTHONPATH=src python -m benchmarks.run --only serving --devices 8

The ``million`` section is the scale story: a synthetic 1M-user / 100k-POI
world served from the `TiledFactorStore` (HBM-resident per-user candidate
windows; the full (I, J, K) factor tensor would be 3.2 TB) through the
tiled window kernel, in fp32 / int8 / bf16. Exactness is cross-checked
against a dense sub-`ServingEngine` rebuilt bitwise-identically on sampled
users (fp32 must match exactly; quantized paths report measured top-k
overlap and max |score delta| vs the analytic bound). ``--tiled-smoke``
runs the same section at toy scale with the assertions live and no JSON
write — the fast-CI entry point.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import dmf, graph
from repro.data import synthetic_poi
from repro.kernels import ops
from repro.serving import (ServingConfig, ServingEngine, SyntheticFactors,
                           TiledFactorStore, TiledServingEngine,
                           build_hierarchical_index, index_from_dataset,
                           synthetic_world)


def _loop_per_request(state, seen, users, k, n_timed):
    """Seed path: per-request Python loop, one kernel call per request."""
    U = state.U
    V = state.P + state.Q
    seen = jnp.asarray(seen)
    u0 = int(users[0])
    ops.recommend_topk(U[u0][None], V[u0], seen[u0][None], k)  # warm/compile
    t0 = time.perf_counter()
    for u in users[:n_timed]:
        u = int(u)
        _, idx = ops.recommend_topk(U[u][None], V[u], seen[u][None], k)
        jax.block_until_ready(idx)
    dt = time.perf_counter() - t0
    return n_timed / dt


def _engine_path(state, index, train, users, k, microbatch, prune,
                 n_shards=1):
    eng = ServingEngine(
        state, index,
        ServingConfig(microbatch=microbatch, k=k, prune=prune,
                      n_shards=n_shards),
        train=train,
    )
    eng.recommend(users[:microbatch])      # warm/compile
    eng.stats.reset()
    _, idx = eng.recommend(users)
    return eng.requests_per_sec, eng.stats.latency_percentiles(), idx


def sharded_section(state, index, train, users, k, microbatch,
                    shard_counts=(1, 2, 4, 8)) -> dict:
    """SPMD engine by shard count: requests/sec, per-dispatch latency, and
    exactness vs the single-shard pruned engine (must be 1.0 — same kernel,
    same rows, just gathered shard-locally). The shards_1 grid entry doubles
    as the exactness reference — deterministic engine, so no separate
    reference pass."""
    n_devices = len(jax.devices())
    assert shard_counts and shard_counts[0] == 1, (
        "shards_1 is the exactness reference and must lead the grid")
    idx_ref = None
    out = {"config": {"n_devices": n_devices, "n_requests": int(len(users)),
                      "microbatch": microbatch},
           "requests_per_sec": {}, "latency_ms": {},
           "exact_match_vs_single_shard": {}}
    for n_shards in shard_counts:
        key = f"shards_{n_shards}"
        if n_shards > n_devices:
            out["requests_per_sec"][key] = None
            out["exact_match_vs_single_shard"][key] = (
                f"skipped: {n_devices} devices")
            continue
        rps, lat, idx = _engine_path(state, index, train, users, k,
                                     microbatch, prune=True,
                                     n_shards=n_shards)
        if idx_ref is None:
            idx_ref = idx
        out["requests_per_sec"][key] = rps
        out["latency_ms"][key] = lat
        out["exact_match_vs_single_shard"][key] = float(
            (np.asarray(idx) == np.asarray(idx_ref)).all(axis=1).mean())
    return out


def _tiled_rps(eng, users, warm=64):
    eng.recommend(users[:warm])
    eng.stats.reset()
    vals, idx, flags = eng.recommend(users, return_flags=True)
    return eng.requests_per_sec, vals, idx, flags


def million_section(n_users=1_000_000, n_items=100_000, n_cities=1024,
                    dim=8, cell_cap=128, n_requests=2048, n_oracle=32,
                    microbatch=128, k=10, seed=0) -> dict:
    """Serve a synthetic ``n_users`` × ``n_items`` world from the tiled
    store. Reports build times, resident bytes per precision, requests/sec
    for fp32 / int8 / bf16, the flat-vs-hierarchical cap reduction that
    makes the slab fit at all, and the exactness block (fp32 bitwise vs a
    dense sub-engine on sampled users; quantized overlap + measured delta
    vs the analytic bound). The returned dict IS asserted on: callers rely
    on exact.fp32_bitwise_vs_dense_engine being True."""
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    uc, ic, ucoord, icoord = synthetic_world(n_users, n_items, n_cities,
                                             seed=seed)
    t_world = time.perf_counter() - t0
    t0 = time.perf_counter()
    hier = build_hierarchical_index(ic, uc, icoord, ucoord, cell_cap=cell_cap)
    t_index = time.perf_counter() - t0
    # what the flat city index would have needed (the hierarchy's raison
    # d'être: slab bytes scale linearly with cap)
    biggest_city = int(np.bincount(ic, minlength=n_cities).max())
    t0 = time.perf_counter()
    synth = SyntheticFactors.create(n_users, n_items, dim, seed=seed + 1)
    store = TiledFactorStore.synthetic(synth, hier.flat, seen_per_user=2,
                                       seed=seed + 2)
    t_store = time.perf_counter() - t0
    t0 = time.perf_counter()
    store.quantize_int8()
    store.quantize_bf16()
    t_quant = time.perf_counter() - t0

    users = rng.integers(0, n_users, n_requests)
    cfg = ServingConfig(microbatch=microbatch, k=k)
    rps = {}
    served = {}
    for mode in ("fp32", "int8", "bf16"):
        eng = TiledServingEngine(store, cfg, mode=mode)
        rps[mode], *served_m = _tiled_rps(eng, users)
        served[mode] = served_m
    vals_f, idx_f, flags = served["fp32"]

    # --- exactness: dense sub-engine on sampled users, rebuilt so its
    # pruned path runs the SAME kernel computation on the SAME floats
    # (P = dense generator rows, Q = 0; seen scattered from the store
    # windows). Sampled among non-fallback users so both sides serve the
    # factor path, not the popularity slate.
    pool = np.flatnonzero(~store.cold
                          & (hier.flat.bucket_size[hier.flat.user_bucket] > 0))
    sample = rng.choice(pool, size=min(n_oracle, len(pool)), replace=False)
    n = len(sample)
    dense = synth.dense_rows(sample)                       # (n, J, K)
    sub_state = dmf.DMFState(
        U=jnp.asarray(store.U[sample]),
        P=jnp.asarray(dense),
        Q=jnp.zeros_like(dense),
    )
    seen_sub = np.zeros((n, n_items), bool)
    cand_s = hier.flat.bucket_items[hier.flat.user_bucket[sample]]
    for r in range(n):
        m = (cand_s[r] >= 0) & (store.seen[sample[r]] != 0)
        seen_sub[r, cand_s[r][m]] = True
    sub_index = dataclasses.replace(
        hier.flat, user_bucket=hier.flat.user_bucket[sample])
    sub_eng = ServingEngine(sub_state, sub_index,
                            ServingConfig(microbatch=min(microbatch, n), k=k),
                            seen=seen_sub)
    v_ref, i_ref, f_ref = sub_eng.recommend(np.arange(n), return_flags=True)
    teng = TiledServingEngine(store, cfg)
    v_t, i_t, f_t = teng.recommend(sample, return_flags=True)
    assert not f_ref.any() and not f_t.any()
    fp32_bitwise = bool((np.asarray(i_ref) == i_t).all()
                        and (np.asarray(v_ref) == v_t).all())
    assert fp32_bitwise, "tiled fp32 diverged from the dense sub-engine"

    # quantized: measured top-k score delta vs the per-request analytic
    # bound, and slate overlap vs fp32, on the same sampled users
    exact = {"n_oracle_users": int(n),
             "fp32_bitwise_vs_dense_engine": fp32_bitwise}
    for mode, bound in [("int8", store.int8_score_bound(sample)),
                        ("bf16", store.bf16_score_bound(sample))]:
        qe = TiledServingEngine(store, cfg, mode=mode)
        vq, iq, fq = qe.recommend(sample, return_flags=True)
        overlap = np.fromiter(
            (len(set(a[a >= 0]) & set(b[b >= 0])) / max((a >= 0).sum(), 1)
             for a, b in zip(np.asarray(i_t), iq)), np.float64, n)
        worst = 0.0
        for r in range(n):
            sc = store.slab[sample[r]] @ store.U[sample[r]]
            for slot in range(k):
                j = iq[r, slot]
                if j < 0:
                    continue
                pos = int(np.flatnonzero(cand_s[r] == j)[0])
                worst = max(worst, abs(float(vq[r, slot]) - float(sc[pos])))
        assert worst <= float(bound.max()) + 1e-6, (mode, worst, bound.max())
        exact[mode] = {
            "topk_overlap_vs_fp32": float(overlap.mean()),
            "max_abs_score_delta": worst,
            "analytic_bound_max": float(bound.max()),
        }

    nb = store.nbytes()
    return {
        "config": {"n_users": n_users, "n_items": n_items,
                   "n_cities": n_cities, "dim": dim, "cell_cap": cell_cap,
                   "n_requests": int(n_requests), "microbatch": microbatch,
                   "k": k},
        "index": {"n_cells": hier.n_cells, "cap": hier.flat.cap,
                  "max_depth": hier.max_depth,
                  "flat_city_cap_would_be": biggest_city,
                  "cap_reduction_vs_flat":
                      biggest_city / max(hier.flat.cap, 1)},
        "build_seconds": {"world": t_world, "index": t_index,
                          "store": t_store, "quantize": t_quant},
        "resident_gb": {kk: v / 1e9 for kk, v in nb.items()},
        "requests_per_sec": rps,
        "fallback_frac": float(flags.mean()),
        "exact": exact,
    }


def tiled_smoke() -> dict:
    """Toy-scale million section for fast CI: every exactness assertion
    live (fp32 bitwise vs dense sub-engine, quantized delta within the
    analytic bound), no JSON written, seconds not minutes."""
    return million_section(n_users=4096, n_items=1024, n_cities=16,
                           dim=8, cell_cap=128, n_requests=256,
                           n_oracle=24, microbatch=64)


def main(full: bool = False, tiny: bool = False) -> dict:
    if tiny:
        ds = synthetic_poi.generate(synthetic_poi.POIDatasetConfig(
            n_users=128, n_items=96, n_ratings=900, n_cities=4))
    else:
        ds = synthetic_poi.foursquare_like(reduced=not full)
    gcfg = graph.GraphConfig(n_neighbors=2, walk_length=3)
    W = graph.build_adjacency(ds.user_coords, ds.user_city, gcfg)
    nbr = graph.walk_neighbor_table(W, gcfg)
    cfg = dmf.DMFConfig(n_users=ds.n_users, n_items=ds.n_items, dim=10,
                        beta=0.1, gamma=0.01)
    res = dmf.fit(cfg, ds.train, nbr, epochs=40 if full else (6 if tiny else 20))
    index = index_from_dataset(ds)

    from repro.core import metrics as metrics_lib
    seen = metrics_lib.masks_from_interactions(ds.n_users, ds.n_items, ds.train)

    k = 10
    microbatch = 16 if tiny else 64
    n_requests = 64 if tiny else (256 if not full else 1024)
    n_loop = 8 if tiny else (32 if not full else 64)  # loop path slow by design
    rng = np.random.default_rng(0)
    users = rng.integers(0, ds.n_users, n_requests)

    rps_loop = _loop_per_request(res.state, seen, users, k, n_loop)
    rps_dense, lat_dense, idx_dense = _engine_path(
        res.state, index, ds.train, users, k, microbatch, prune=False)
    rps_pruned, lat_pruned, idx_pruned = _engine_path(
        res.state, index, ds.train, users, k, microbatch, prune=True)

    # pruning fidelity. Two regimes: where the dense full-J top-k already
    # lies inside the user's city bucket, pruning must be EXACT (asserted
    # in tests/test_serving.py). Elsewhere the difference is score-tie
    # spillover: untouched items score exactly u·0 = 0, so users short of k
    # positively-scored city candidates fill dense slots with lowest-id
    # 0.0-ties from any city — the pruned path keeps those in-city instead.
    agree = np.fromiter(
        ((set(a[a >= 0]) == set(b[b >= 0]))
         for a, b in zip(idx_pruned, idx_dense)), bool, len(users))
    in_bucket = np.fromiter(
        (bool(np.isin(d[d >= 0],
                      index.bucket_items[index.user_bucket[u]]).all())
         for u, d in zip(users, idx_dense)), bool, len(users))

    res_json = {
        "config": {
            "n_users": ds.n_users, "n_items": ds.n_items, "dim": cfg.dim,
            "k": k, "microbatch": microbatch, "n_requests": int(n_requests),
            "n_loop_requests": int(n_loop),
            "bucket_cap": index.cap, "n_buckets": index.n_buckets,
            "n_truncated_buckets": index.n_truncated_buckets,
        },
        "requests_per_sec": {
            "loop_per_request": rps_loop,
            "batched_dense": rps_dense,
            "batched_pruned": rps_pruned,
        },
        "latency_ms": {
            "batched_dense": lat_dense,
            "batched_pruned": lat_pruned,
        },
        "speedup_pruned_vs_loop": rps_pruned / rps_loop,
        "speedup_pruned_vs_dense": rps_pruned / rps_dense,
        "pruned_dense_topk_agreement": float(agree.mean()),
        "dense_topk_in_bucket_frac": float(in_bucket.mean()),
        "pruned_dense_topk_agreement_where_in_bucket": float(
            agree[in_bucket].mean() if in_bucket.any() else 1.0),
    }
    # the serving tentpole contract, pinned in the artifact: the tiled
    # window kernel (per-request candidate windows only) is bit-identical
    # to the whole-slab kernel on the bench's own pruned requests
    V = np.asarray(res.state.P + res.state.Q)
    wu = users[:microbatch]
    cand_w = index.bucket_items[index.user_bucket[wu]]
    safe_w = np.maximum(cand_w, 0)
    vw = V[wu[:, None], safe_w]
    sw = np.where(cand_w >= 0, seen[wu[:, None], safe_w], False
                  ).astype(np.int8)
    tv, ti = ops.serve_topk_window(np.asarray(res.state.U)[wu], vw,
                                   cand_w, sw, k)
    sv, si = ops.serve_topk(jnp.asarray(res.state.U)[jnp.asarray(wu)],
                            jnp.asarray(V)[jnp.asarray(wu)],
                            jnp.asarray(cand_w),
                            jnp.asarray(seen)[jnp.asarray(wu)], k)
    res_json["tiled_kernel_bit_identical_vs_slab"] = bool(
        (np.asarray(ti) == np.asarray(si)).all()
        and (np.asarray(tv) == np.asarray(sv)).all())
    assert res_json["tiled_kernel_bit_identical_vs_slab"]

    # SPMD engine by shard count (more requests: each dispatch serves
    # microbatch×shards, so the single-shard request count undersamples)
    sh_users = rng.integers(0, ds.n_users, n_requests * 4)
    res_json["sharded"] = sharded_section(
        res.state, index, ds.train, sh_users, k, microbatch)
    # million-user tiled-store section (toy-sized under tiny so the bench
    # smoke stays fast; real 1M × 100k otherwise)
    if tiny:
        res_json["million"] = tiled_smoke()
    else:
        res_json["million"] = million_section(
            n_requests=4096 if full else 2048)
    common.save_json("BENCH_serving", res_json)   # mirrors to repo root
    return res_json


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true",
                    help="full-scale dataset + more requests")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes (bench smoke scale)")
    ap.add_argument("--tiled-smoke", action="store_true",
                    help="run only the toy-scale tiled/million section with "
                         "its exactness assertions; no JSON written (CI)")
    cli = ap.parse_args()
    if cli.tiled_smoke:
        print(json.dumps(tiled_smoke(), indent=1))
    else:
        print(json.dumps(main(full=cli.full, tiny=cli.tiny), indent=1))
