"""Serving traffic: an open loop on the wall clock into
`ServingEngine.serve_microbatch`, one process, one thread.

Set-up builds the data, makes the factors U, P, Q on the device from the
seed in one jitted call, builds the engine with the program's defaults and
the city candidate index, and warms its one dispatch shape. The window's
arrivals and users are drawn from the seed before it opens. The loop takes
whatever is due, up to the microbatch, and serves it; a request's latency
runs from when it was due until its slate is back on the host. Requests
due in the window are drained for at most `DRAIN_S` after it; one that is
never served is missing.
"""
from __future__ import annotations

import contextlib
import functools
import time

import numpy as np

from perfbench import arrivals, checks, data
from perfbench.refs import dmf as ref

DRAIN_S = 60.0


def sub_seed(seed: int, stream: int) -> int:
    return int(np.random.SeedSequence([seed % 2**64, stream]).generate_state(
        1, np.uint32)[0])


@functools.lru_cache(maxsize=None)
def _factor_maker(I: int, J: int, K: int, scale: float, live_share: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, city_live, user_city):
        ku, kp, kq, kl = jax.random.split(key, 4)
        f32 = jnp.float32
        live = (city_live[user_city]
                | (jax.random.uniform(kl, (I, J)) < live_share))[:, :, None]
        return (scale * jax.random.normal(ku, (I, K), f32),
                jnp.where(live, scale * jax.random.normal(kp, (I, J, K), f32), 0.0),
                jnp.where(live, scale * jax.random.normal(kq, (I, J, K), f32), 0.0))
    return make


def city_live(ds) -> np.ndarray:
    """(C, J): the POIs some user of the city checked in to in training."""
    C = int(max(ds.user_city.max(), ds.item_city.max())) + 1
    live = np.zeros((C, ds.n_items), bool)
    live[ds.user_city[ds.train[:, 0]], ds.train[:, 1]] = True
    return live


def make_factors(seed: int, ds, shape):
    """The served factors, made on the device from the seed. A trained
    model's p^i_j and q^i_j stay exactly 0 where no message about POI j
    reached user i, so its scores tie at exactly 0 and the tie rule decides
    slates. These factors keep such zeros: U, P, Q are normal with the
    traffic's scale, and P, Q are 0 except for the POIs that a user of the
    same city checked in to and a ``live_share`` of the rest, drawn from
    the seed (the share a 20-epoch job's negatives reach, see PERF.md)."""
    import jax
    return _factor_maker(*shape)(jax.random.key(sub_seed(seed, 1)),
                                 city_live(ds), ds.user_city)


class Session:
    def __init__(self, cell, seed: int, seconds: float):
        from repro.core.dmf import DMFState
        from repro.serving import (ServingConfig, ServingEngine,
                                   build_candidate_index)
        self.cell, self.seed = cell, seed
        cfg, trf = cell.config, cell.traffic
        self.ds = ds = data.from_config(cfg)
        I, J, K = ds.n_users, ds.n_items, cfg["model"]["dim"]
        self.shape = (I, J, K, float(trf["factor_scale"]),
                      float(trf["live_share"]))
        U, P, Q = make_factors(seed, ds, self.shape)
        index = build_candidate_index(ds.item_city, ds.user_city, n_items=J)
        self.R, self.k = int(trf["microbatch"]), int(trf["k"])
        self.engine = ServingEngine(
            DMFState(U=U, P=P, Q=Q), index,
            ServingConfig(microbatch=self.R, k=self.k), train=ds.train)
        del U, P, Q
        rng = np.random.default_rng(sub_seed(seed, 2))
        self.load(trf, seconds, rng)
        warm = arrivals.window_users(trf, int(trf["warm_dispatches"]) * self.R,
                                     I, rng)
        for s in range(0, len(warm), self.R):
            self.engine.serve_microbatch(warm[s:s + self.R])

    def load(self, traffic: dict, seconds: float, rng) -> None:
        """Draw the window's arrivals and users."""
        self.times = arrivals.window_arrivals(traffic, seconds, rng)
        self.users = arrivals.window_users(traffic, len(self.times),
                                           self.ds.n_users, rng)

    def window(self, seconds: float, annotate=contextlib.nullcontext) -> dict:
        times = self.times[self.times < seconds]
        users = self.users[:len(times)]
        n, R = len(times), self.R
        ids = np.full((n, self.k), -1, np.int32)
        vals = np.zeros((n, self.k), np.float32)
        start = np.full(n, np.nan)
        done = np.full(n, np.nan)
        disp = []
        serve = self.engine.serve_microbatch
        clock = time.perf_counter
        i = 0
        with annotate():
            t0 = clock()
            while i < n:
                now = clock() - t0
                due = int(np.searchsorted(times, now, side="right"))
                if due <= i:
                    wait = times[i] - now
                    if wait > 2e-4:
                        time.sleep(wait - 1e-4)
                    continue
                if now > seconds + DRAIN_S:
                    break
                j = min(due, i + R)
                ts = clock()
                v, x, _ = serve(users[i:j])
                te = clock()
                ids[i:j], vals[i:j] = x, v
                start[i:j], done[i:j] = ts - t0, te - t0
                disp.append((ts - t0, te - t0, j - i))
                i = j
            t_end = clock() - t0
        return {"window_s": float(seconds), "loop_s": t_end, "times": times,
                "users": users, "ids": ids, "vals": vals, "start": start,
                "done": done, "dispatches": np.asarray(disp, np.float64),
                "microbatch": R}

    def end_to_end(self, rec: dict) -> dict:
        lat = rec["done"] - rec["times"]
        lat = np.where(np.isnan(lat), np.inf, lat)
        p95 = float(np.percentile(lat, 95)) if len(lat) else np.inf
        served = np.count_nonzero(rec["done"] <= rec["window_s"])
        return {"serve_p95_ms": 1e3 * p95 if np.isfinite(p95) else checks.MISSING,
                "serve_rps": served / rec["window_s"]}

    def layer_inputs(self, rec: dict) -> dict:
        """The window's record, with each request's candidate count (its
        home city's POIs; none for a popularity slate) from the data."""
        ds = self.ds
        city_items = np.bincount(ds.item_city, minlength=ds.user_city.max() + 1)
        cold = np.bincount(ds.train[:, 0], minlength=ds.n_users) == 0
        cand = city_items[ds.user_city[rec["users"]]]
        cand = np.where(cold[rec["users"]], 0, cand)
        return {**rec, "kind": "serve", "dim": self.cell.config["model"]["dim"],
                "k": self.k, "candidates": cand}

    def release(self) -> None:
        self.engine = None

    def sample(self, rec: dict) -> np.ndarray:
        """Sorted indices of the served requests that `check` compares,
        drawn from the seed."""
        served = np.flatnonzero(~np.isnan(rec["done"]))
        n = min(int(self.cell.traffic["check_requests"]), len(served))
        rng = np.random.default_rng(sub_seed(self.seed, 3))
        return np.sort(rng.choice(served, n, replace=False))

    def check(self, rec: dict):
        import gc
        gc.collect()
        pick = self.sample(rec)
        numbers = compare(self.ds, self.shape, self.seed, rec["users"][pick],
                          rec["ids"][pick], rec["vals"][pick], self.k)
        failed = int(np.isnan(rec["done"]).sum())
        return numbers, len(rec["times"]), failed


def compare(ds, shape, seed, users, ids, vals, k, dtype=None) -> dict:
    """The reference's slates of ``users`` over the seed's factors, against
    the served ``ids``/``vals``; with ``dtype`` the served slates are
    instead the reference's own, computed in that precision (the control)."""
    import jax.numpy as jnp
    server = ref.Server(ds, k)
    U, P, Q = make_factors(seed, ds, shape)
    cand, fallback = server.candidates(users)
    scores, mag = ref.Server.scores(U, P, Q, users, cand)
    if dtype is not None:
        low, _ = ref.Server.scores(U, P, Q, users, cand, dtype=jnp.dtype(dtype))
        ids = server.topk(low, cand)
        vals = np.take_along_axis(
            np.concatenate([low, np.zeros((len(low), 1), np.float32)], 1),
            np.where(ids >= 0, _pos(cand, ids), low.shape[1]), 1)
        ids = np.where(fallback[:, None], server.pop_ids[None], ids)
        vals = np.where(fallback[:, None], server.pop_vals[None], vals)
    del U, P, Q
    want = server.topk(scores, cand)
    return checks.serve_numbers(ids, vals, want, fallback, server.pop_ids,
                                server.pop_vals, cand, scores, mag)


def _pos(cand, ids):
    """Column of each id in its row of ``cand`` (ids present in the row)."""
    out = np.zeros(ids.shape, np.int64)
    for r in range(len(ids)):
        ok = cand[r] >= 0
        out[r] = np.searchsorted(cand[r][ok], np.maximum(ids[r], 0))
    return out
