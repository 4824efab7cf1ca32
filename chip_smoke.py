#!/usr/bin/env python3
"""Bring-up check on a TPU: DMF training, evaluation and serving at the
paper's Foursquare Table 1 scale, through the entry points a user calls,
each checked against the repo's plain references.

    python chip_smoke.py             # one chip, phases (a)-(e)
    python chip_smoke.py --chips 4   # four chips: learner-sharded path only

One chip:
  (a) device — the default backend must be a TPU, else exit 1 here;
  (b) training — `dmf.fit` for 3 epochs with the jnp step and 3 with the
      fused Pallas step from the same seed: per-epoch losses agree;
  (c) evaluation — `dmf.evaluate` through the compiled streaming top-k, the
      kernel's slates under the serving contract (`ref.assert_topk_matches`)
      against `ref.topk_scores_peruser_ref` for every user, P@k/R@k equal;
  (d) pruned serving — `ServingEngine` (microbatch 64, k=10) on the trained
      state against `ref.serve_topk_window_ref`, then `ingest` of fresh
      check-ins and the same check on the refreshed state;
  (e) tiled serving — the 1M x 100k, K=8 `TiledFactorStore` on the device,
      fp32 against a dense sub-engine and the window oracle, int8 within its
      analytic score bound.
Four chips: `fit`/`evaluate` with n_shards=4 on the `learners` mesh against
n_shards=1, and the 4-shard `ServingEngine` against the 1-shard one.

Every phase prints its wall time, labelled set-up (compilation included;
these are not performance numbers), and the devices' memory. Any failed
phase or comparison exits 1. The last line of stdout is the JSON object
{"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

SRC = pathlib.Path(__file__).resolve().parent / "src"
EPOCHS = 3
LOSS_RTOL = 1e-4          # the training CLI's bound between step paths
K_TOP = 10
FOURSQUARE_FULL = True    # Table 1 scale (False: the reduced CI world)
TILED = dict(n_users=1_000_000, n_items=100_000, dim=8, n_cities=1024)


def log(msg: str) -> None:
    print(msg, flush=True)


def memory_line() -> str:
    import jax
    parts = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:in_use={st.get('bytes_in_use')}"
                     f",peak={st.get('peak_bytes_in_use')}")
    return "memory_bytes " + " ".join(parts)


class Phase:
    """Times one phase and reports it; an exception fails the run."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"phase {self.name}: start")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, etype, exc, tb):
        dt = time.perf_counter() - self.t0
        if etype is not None:
            log(f"phase {self.name}: FAILED after {dt:.3f} s set-up wall")
            traceback.print_exception(etype, exc, tb)
            sys.exit(1)
        log(f"phase {self.name}: ok setup_wall_s={dt:.3f} (compile included)")
        log(f"phase {self.name}: {memory_line()}")


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        raise RuntimeError(f"no TPU: the default backend is {info['platform']}")
    if info["count"] < chips:
        raise RuntimeError(f"--chips {chips} but {info['count']} devices")
    return info


def foursquare(seed: int):
    """Table 1 Foursquare world, its walk neighbour table and the paper's
    hyper-parameters (configs/dmf_foursquare.py)."""
    from repro.configs import dmf_foursquare as fs
    from repro.core import graph
    from repro.data import synthetic_poi
    ds = synthetic_poi.foursquare_like(reduced=not FOURSQUARE_FULL, seed=seed)
    W = graph.build_adjacency(ds.user_coords, ds.user_city, fs.GRAPH)
    nbr = graph.walk_neighbor_table(W, fs.GRAPH)
    cfg = dataclasses.replace(fs.dmf_config(ds.n_users, ds.n_items),
                              seed=seed)
    log(f"data users={ds.n_users} items={ds.n_items} train={len(ds.train)} "
        f"test={len(ds.test)} cities={ds.config.n_cities} "
        f"neighbour_width={nbr.idx.shape[1]}")
    return ds, nbr, cfg


def losses_agree(a, b, what: str) -> None:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.isfinite(a).all() and np.isfinite(b).all(), (what, a, b)
    rel = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
    log(f"{what}: losses {a.tolist()} vs {b.tolist()} "
        f"max_rel_diff={rel.max():.3e}")
    assert rel.max() <= LOSS_RTOL, (what, rel.max())


def check_evaluate(state, ds) -> dict:
    """`dmf.evaluate` plus the kernel's slates against the dense oracle."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dmf, metrics
    from repro.kernels import ops, ref
    I, J = ds.n_users, ds.n_items
    ev = dmf.evaluate(state, ds.train, ds.test, I, J, ks=(5, 10))
    train_mask = jnp.asarray(metrics.masks_from_interactions(I, J, ds.train))
    test_mask = metrics.masks_from_interactions(I, J, ds.test)
    V = state.P + state.Q
    vals, idx = ops.recommend_topk_peruser(state.U, V, train_mask, K_TOP)
    v_ref, i_ref = ref.masked_topk_finalize(
        *ref.topk_scores_peruser_ref(state.U, V, train_mask, K_TOP))
    scores = ref.peruser_scores(state.U, V, train_mask)
    rep = ref.assert_topk_matches(vals, idx, v_ref, i_ref, state.U, V,
                                  ref_scores=scores)
    ev_kernel = metrics.evaluate_ranking_from_topk(np.asarray(idx), test_mask,
                                                   (5, 10))
    ev_ref = metrics.evaluate_ranking_from_topk(np.asarray(i_ref), test_mask,
                                                (5, 10))
    log(f"evaluate users={I} contract={rep} metrics={ev} oracle={ev_ref}")
    assert ev == ev_kernel == ev_ref, (ev, ev_kernel, ev_ref)
    return ev


def by_item(win, cand, n_items: int):
    """Window scores (n, Cw) scattered to item-id columns (n, J), NEG_INF
    elsewhere — the ``ref_scores`` layout of `ref.assert_topk_matches`."""
    import numpy as np

    from repro.kernels import ref
    win, cand = np.asarray(win), np.asarray(cand)
    full = np.full((len(cand), n_items), ref.NEG_INF, np.float32)
    rows = np.repeat(np.arange(len(cand)), cand.shape[1])
    keep = cand.ravel() >= 0
    full[rows[keep], cand.ravel()[keep]] = win.ravel()[keep]
    return full


def check_served(eng, users, vals, idx, flags, what: str) -> None:
    """Unflagged slates of a `ServingEngine` against the window oracle on
    the engine's own (possibly refreshed) factors and seen bits."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    live = ~flags
    u = users[live]
    cand = eng.index.bucket_items[eng.index.user_bucket[u]]
    safe = jnp.asarray(np.maximum(cand, 0))
    uj = jnp.asarray(u)[:, None]
    Uw, Vw, sw = eng.state.U[u], eng.V[uj, safe], eng.seen[uj, safe]
    v_ref, i_ref = ref.serve_topk_window_ref(Uw, Vw, cand, sw, K_TOP)
    rep = ref.assert_topk_matches(
        vals[live], idx[live], v_ref, i_ref, Uw, Vw,
        ref_scores=by_item(ref.window_scores(Uw, Vw, cand, sw), cand,
                           eng.index.n_items))
    log(f"{what}: requests={len(users)} fallback={int(flags.sum())} "
        f"contract={rep}")


def phase_serving(state, ds, nbr, cfg, rng) -> None:
    import numpy as np

    from repro.serving import ServingConfig, ServingEngine, index_from_dataset
    index = index_from_dataset(ds)
    eng = ServingEngine(state, index, ServingConfig(microbatch=64, k=K_TOP),
                        train=ds.train, nbr=nbr, dmf_cfg=cfg)
    users = rng.integers(0, ds.n_users, 512)
    vals, idx, flags = eng.recommend(users, return_flags=True)
    check_served(eng, users, vals, idx, flags, "serve cap=%d" % index.cap)
    # fresh check-ins: unseen items of each user's own candidate bucket
    seen = np.asarray(eng.seen).astype(bool)
    events = []
    for u in rng.permutation(users)[:300]:
        c = index.bucket_items[index.user_bucket[u]]
        c = c[(c >= 0) & ~seen[u, np.maximum(c, 0)]]
        if len(c):
            events.append((int(u), int(rng.choice(c))))
    events = np.asarray(events, np.int64)
    rep = eng.ingest(events)
    log(f"ingest events={len(events)} touched_users={len(rep.touched_users)} "
        f"losses={[round(x, 6) for x in rep.losses]}")
    assert np.isfinite(rep.losses).all(), rep.losses
    again = np.concatenate([events[:, 0], users[:512 - len(events)]])
    vals, idx, flags = eng.recommend(again, return_flags=True)
    check_served(eng, again, vals, idx, flags, "serve after ingest")
    for (u, j), row, fl in zip(events, idx, flags):
        assert fl or j not in row, f"user {u} was recommended item {j} just visited"


def phase_tiled(seed: int, rng) -> None:
    """The 1M x 100k, K=8 synthetic deployment (README, million-user
    serving) with its fp32 and int8 slabs on the device."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dmf
    from repro.kernels import ref
    from repro.serving import (ServingConfig, ServingEngine, SyntheticFactors,
                               TiledFactorStore, TiledServingEngine,
                               build_hierarchical_index, synthetic_world)
    I, J, K, n_cities = (TILED[k] for k in ("n_users", "n_items", "dim",
                                             "n_cities"))
    t0 = time.perf_counter()
    uc, ic, ucoord, icoord = synthetic_world(I, J, n_cities, seed=seed)
    hier = build_hierarchical_index(ic, uc, icoord, ucoord, cell_cap=128)
    synth = SyntheticFactors.create(I, J, K, seed=seed + 1)
    store = TiledFactorStore.synthetic(synth, hier.flat, seen_per_user=2,
                                       seed=seed + 2)
    store.quantize_int8()
    log(f"tiled store host build {time.perf_counter() - t0:.3f} s "
        f"cap={store.cap} cells={hier.n_cells} "
        f"bytes={ {k: int(v) for k, v in store.nbytes().items()} }")
    cfg = ServingConfig(microbatch=128, k=K_TOP)
    fp = TiledServingEngine(store, cfg, mode="fp32")
    q8 = TiledServingEngine(store, cfg, mode="int8")
    log(f"tiled engines placed: {memory_line()}")
    users = rng.integers(0, I, 384)
    vf, i_f, ff = fp.recommend(users, return_flags=True)
    vq, iq, fq = q8.recommend(users, return_flags=True)
    assert (ff == fq).all() and (i_f[~ff] >= 0).any()
    # fp32 against a dense sub-engine on sampled users (its pruned path runs
    # the same kernel on the same floats: P = dense generator rows, Q = 0)
    pool = np.flatnonzero(~store.cold
                          & (hier.flat.bucket_size[hier.flat.user_bucket] > 0))
    sample = rng.choice(pool, size=64, replace=False)
    dense = synth.dense_rows(sample)
    sub_state = dmf.DMFState(U=jnp.asarray(store.U[sample]),
                             P=jnp.asarray(dense), Q=jnp.zeros(dense.shape))
    cand = hier.flat.bucket_items[hier.flat.user_bucket[sample]]
    seen_sub = np.zeros((len(sample), J), bool)
    for r, u in enumerate(sample):
        m = (cand[r] >= 0) & (store.seen[u] != 0)
        seen_sub[r, cand[r][m]] = True
    sub = ServingEngine(sub_state, dataclasses.replace(
        hier.flat, user_bucket=hier.flat.user_bucket[sample]),
        ServingConfig(microbatch=64, k=K_TOP), seen=seen_sub)
    v_sub, i_sub, f_sub = sub.recommend(np.arange(len(sample)),
                                        return_flags=True)
    v_t, i_t, f_t = fp.recommend(sample, return_flags=True)
    assert not f_sub.any() and not f_t.any()
    np.testing.assert_array_equal(i_t, i_sub)
    np.testing.assert_array_equal(v_t, v_sub)
    Us, Vw, sw = store.U[sample], store.slab[sample], store.seen[sample]
    v_ref, i_ref = ref.serve_topk_window_ref(Us, Vw, cand, sw, K_TOP)
    rep = ref.assert_topk_matches(
        v_t, i_t, v_ref, i_ref, Us, Vw,
        ref_scores=by_item(ref.window_scores(Us, Vw, cand, sw), cand, J))
    log(f"tiled fp32 requests={len(users)} fallback={int(ff.sum())} "
        f"sub_engine=bitwise oracle_contract={rep}")
    # int8: every served score within the analytic bound of its fp32 score
    vq_s, iq_s, _ = q8.recommend(sample, return_flags=True)
    bound = store.int8_score_bound(sample)
    worst = 0.0
    for r, u in enumerate(sample):
        sc = store.slab[u] @ store.U[u]
        for slot in range(K_TOP):
            j = iq_s[r, slot]
            if j < 0:
                continue
            pos = int(np.flatnonzero(cand[r] == j)[0])
            err = abs(float(vq_s[r, slot]) - float(sc[pos]))
            assert err <= bound[r] + 1e-6, (u, slot, err, bound[r])
            worst = max(worst, err)
    log(f"tiled int8 requests={len(users)} max_abs_score_delta={worst:.6g} "
        f"analytic_bound_max={float(bound.max()):.6g}")


def one_chip(seed: int) -> None:
    import numpy as np

    from repro.core import dmf
    rng = np.random.default_rng(seed)
    with Phase("b-training"):
        ds, nbr, cfg = foursquare(seed)
        res_jnp = dmf.fit(cfg, ds.train, nbr, epochs=EPOCHS, seed=seed)
        losses_jnp = list(res_jnp.train_losses)
        del res_jnp
        res = dmf.fit(dataclasses.replace(cfg, use_pallas=True), ds.train,
                      nbr, epochs=EPOCHS, seed=seed)
        losses_agree(res.train_losses, losses_jnp, "pallas vs jnp step")
    with Phase("c-evaluation"):
        check_evaluate(res.state, ds)
    with Phase("d-pruned-serving"):
        phase_serving(res.state, ds, nbr,
                      dataclasses.replace(cfg, use_pallas=True), rng)
    del res
    with Phase("e-tiled-serving"):
        phase_tiled(seed, rng)


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import dmf
    from repro.kernels import ref
    from repro.serving import ServingConfig, ServingEngine, index_from_dataset
    rng = np.random.default_rng(seed)
    with Phase("sharded-training"):
        ds, nbr, cfg = foursquare(seed)
        sh = dmf.fit(dataclasses.replace(cfg, n_shards=4), ds.train, nbr,
                     epochs=EPOCHS, seed=seed)
        log(f"sharded state P sharding={sh.state.P.sharding} "
            f"shard_bytes={[s.data.nbytes for s in sh.state.P.addressable_shards]}")
        ev4 = dmf.evaluate(sh.state, ds.train, ds.test, ds.n_users,
                           ds.n_items, n_shards=4)
        on_one = jax.device_put(sh.state, jax.devices()[0])
        ev4_on1 = dmf.evaluate(on_one, ds.train, ds.test, ds.n_users,
                               ds.n_items)
        log(f"evaluate n_shards=4 {ev4} vs n_shards=1 on the same state "
            f"{ev4_on1}")
        assert ev4 == ev4_on1, (ev4, ev4_on1)
    with Phase("sharded-serving"):
        index = index_from_dataset(ds)
        users = rng.integers(0, ds.n_users, 512)
        out = {}
        for n, st in ((4, sh.state), (1, on_one)):
            eng = ServingEngine(st, index,
                                ServingConfig(microbatch=64, k=K_TOP,
                                              n_shards=n), train=ds.train)
            out[n] = eng.recommend(users, return_flags=True)
            del eng
        (v4, i4, f4), (v1, i1, f1) = out[4], out[1]
        assert (f4 == f1).all()
        rep = ref.assert_topk_matches(
            v4, i4, v1, i1, np.asarray(sh.state.U)[users],
            np.asarray(sh.state.P[users] + sh.state.Q[users]))
        log(f"engine n_shards=4 vs 1: requests={len(users)} "
            f"bitwise={bool((v4 == v1).all() and (i4 == i1).all())} "
            f"contract={rep}")
    with Phase("one-shard-reference"):
        one = dmf.fit(cfg, ds.train, nbr, epochs=EPOCHS, seed=seed)
        losses_agree(sh.train_losses, one.train_losses, "n_shards=4 vs 1")
        ev1 = dmf.evaluate(one.state, ds.train, ds.test, ds.n_users,
                           ds.n_items)
        du = float(np.abs(np.asarray(sh.state.U) - np.asarray(one.state.U)).max())
        log(f"evaluate trained n_shards=1 {ev1} vs n_shards=4 {ev4}; "
            f"max |U4 - U1| = {du:.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        log(f"FAIL: the repro package is not at {SRC}")
        sys.exit(1)
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache
    log(f"compile cache {compile_cache.enable()}")
    with Phase("a-device"):
        info = device_check(args.chips)
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
