"""Million-user serving: HBM-resident tiled factor store + quantized engine.

The per-learner factor model (each user i owns an item view v^i = p^i + q^i)
is an (I, J, K) tensor — 3.2 TB of fp32 at I=1M, J=100k, K=8, physically
impossible to materialize. But serving never READS more of v^i than the
user's candidate window: the engine scores exactly the POIs of the user's
geo cell. The `TiledFactorStore` therefore keeps, per user, ONLY that
window:

    slab (I, cap, K)   — v^i at the user's bucket items, column c of row i
                         being the factor of ``bucket_items[bucket(i), c]``
    seen (I, cap) int8 — the user's seen bits, same column alignment
    U    (I, K)        — user factors

With the hierarchical (geohash-cell) index capping buckets at ~128, the 1M
× 100k config fits in ~4 GB fp32 — and int8 codes (+ per-user scale) or
bf16 cut that by 4x / 2x again. A request gathers its (R, cap, K) windows
straight off the slab and runs the tiled serve kernel
(`ops.serve_topk_window` / `serve_topk_window_quant`) — identical compute
to the classic engine's pruned path, so the fp32 store path is bitwise
identical to `ServingEngine.recommend` on the shared support (pinned by
tests and BENCH_serving).

Quantization error budget (measured in BENCH_serving, asserted in tests):

    int8: codes = rint(v / scale), scale = max|v^i| / 127 per user
          ⇒ |Δv| ≤ scale/2        ⇒ |Δscore| ≤ ||u_i||₁ · scale/2
    bf16: round-to-nearest, 8-bit significand ⇒ |Δv| ≤ 2⁻⁸|v|
          ⇒ |Δscore| ≤ Σ_k |u_k·v_k| · 2⁻⁸

Row sharding: `shard_rows` slices the store along `sharding.dmf`'s
ceil-div row layout (`shard_row_slices`), so a fleet of per-shard engines
routes requests with the same ``user // rows_per_shard`` rule as the SPMD
serving mesh — shard-local results are bitwise identical to the unsharded
store (row-parallel, no cross-shard reads).
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.serving.candidates import CandidateIndex
from repro.serving.engine import EngineStats, ServingConfig

_BF16_EPS = 2.0 ** -8     # round-to-nearest relative error bound of bfloat16


def synthetic_world(
    n_users: int, n_items: int, n_cities: int, seed: int = 0,
    zipf_a: float = 0.8, city_sigma: float = 0.03,
):
    """Vectorized million-scale geography (the per-user Python loop in
    `data/synthetic_poi.generate` is unusable at I=1M): zipf-weighted city
    assignment for users and POIs, Gaussian coordinates around each city
    center. Returns (user_city, item_city, user_coords, item_coords)."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n_cities + 1) ** zipf_a
    w /= w.sum()
    user_city = rng.choice(n_cities, size=n_users, p=w).astype(np.int32)
    item_city = rng.choice(n_cities, size=n_items, p=w).astype(np.int32)
    centers = rng.uniform(0.0, 1.0, size=(n_cities, 2))
    user_coords = (centers[user_city]
                   + city_sigma * rng.standard_normal((n_users, 2)))
    item_coords = (centers[item_city]
                   + city_sigma * rng.standard_normal((n_items, 2)))
    return user_city, item_city, user_coords.astype(np.float64), \
        item_coords.astype(np.float64)


@dataclasses.dataclass(frozen=True)
class SyntheticFactors:
    """Deterministic rank-structured factor generator for million-scale
    benches: v^i_j = B1_j · s_i + B2_j from O(J·K) tables, so the dense
    full-J item view of ANY user recomputes exactly (`dense_rows`) — that
    is what lets a 1M-user store be cross-checked bitwise against a small
    dense sub-engine on sampled users."""
    B1: np.ndarray        # (J, K) f32 shared item basis
    B2: np.ndarray        # (J, K) f32 shared item offset
    s_user: np.ndarray    # (I,) f32 per-user blend
    U: np.ndarray         # (I, K) f32 user factors

    @classmethod
    def create(cls, n_users: int, n_items: int, dim: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        return cls(
            B1=rng.standard_normal((n_items, dim)).astype(np.float32),
            B2=(0.1 * rng.standard_normal((n_items, dim))).astype(np.float32),
            s_user=rng.standard_normal(n_users).astype(np.float32),
            U=(rng.standard_normal((n_users, dim)).astype(np.float32)
               / np.float32(np.sqrt(dim))),
        )

    def item_rows(self, users: np.ndarray, items: np.ndarray) -> np.ndarray:
        """v^{users[r]} at ``items[r]`` — items (n, m) int, any values OK
        for negative ids' positions (callers mask). Returns (n, m, K) f32."""
        safe = np.maximum(items, 0)
        return (self.B1[safe] * self.s_user[users][:, None, None]
                + self.B2[safe])

    def dense_rows(self, users: np.ndarray) -> np.ndarray:
        """Full (len(users), J, K) item views — the oracle input for
        bitwise cross-checks of the tiled store at sampled users."""
        return (self.B1[None, :, :] * self.s_user[users][:, None, None]
                + self.B2[None, :, :])


@dataclasses.dataclass
class TiledFactorStore:
    """Per-user candidate-window factor slabs, built on the host; a
    `TiledServingEngine` places the slab of its precision on the device.
    See the module docstring. ``seen`` is column-aligned to
    ``index.bucket_items[index.user_bucket]``; ``cold``/``item_counts``
    carry the engine's graceful-degradation state (same semantics as
    `ServingEngine`: cold = user with no interactions anywhere)."""
    U: np.ndarray                     # (I, K) f32
    slab: np.ndarray                  # (I, cap, K) f32
    seen: np.ndarray                  # (I, cap) int8
    index: CandidateIndex
    cold: np.ndarray                  # (I,) bool
    item_counts: np.ndarray           # (J,) int64 check-in counts
    q_codes: np.ndarray | None = None   # (I, cap, K) int8
    q_scale: np.ndarray | None = None   # (I,) f32, dequant = codes · scale
    slab_bf16: np.ndarray | None = None  # (I, cap, K) bfloat16

    @property
    def n_users(self) -> int:
        return int(self.U.shape[0])

    @property
    def cap(self) -> int:
        return int(self.slab.shape[1])

    @property
    def dim(self) -> int:
        return int(self.U.shape[1])

    def nbytes(self) -> dict[str, int]:
        out = {"U": self.U.nbytes, "slab_fp32": self.slab.nbytes,
               "seen": self.seen.nbytes}
        if self.q_codes is not None:
            out["slab_int8"] = self.q_codes.nbytes + self.q_scale.nbytes
        if self.slab_bf16 is not None:
            out["slab_bf16"] = self.slab_bf16.nbytes
        return out

    # ------------------------------------------------------------ builders
    @classmethod
    def from_state(cls, state, index: CandidateIndex, seen: np.ndarray,
                   chunk_rows: int = 65536) -> "TiledFactorStore":
        """Build from a trained `DMFState` + dense (I, J) seen mask — the
        small-scale path used to cross-check the store against the classic
        engine. Gathers V = P + Q windows chunked (the full V never
        materializes here either)."""
        P = np.asarray(state.P)
        Q = np.asarray(state.Q)
        U = np.asarray(state.U, dtype=np.float32)
        seen = np.asarray(seen).astype(bool)
        I, cap = len(U), index.cap
        slab = np.empty((I, cap, P.shape[2]), np.float32)
        seen_w = np.zeros((I, cap), np.int8)
        for s in range(0, I, chunk_rows):
            e = min(s + chunk_rows, I)
            rows = np.arange(s, e)
            cand = index.bucket_items[index.user_bucket[rows]]
            safe = np.maximum(cand, 0)
            slab[s:e] = P[rows[:, None], safe] + Q[rows[:, None], safe]
            seen_w[s:e] = np.where(
                cand >= 0, seen[rows[:, None], safe], False).astype(np.int8)
        return cls(U=U, slab=slab, seen=seen_w, index=index,
                   cold=~seen.any(axis=1),
                   item_counts=seen.sum(axis=0).astype(np.int64))

    @classmethod
    def synthetic(cls, synth: SyntheticFactors, index: CandidateIndex,
                  seen_per_user: int = 4, seed: int = 0,
                  chunk_rows: int = 131072) -> "TiledFactorStore":
        """Million-scale builder: fill the slab from the rank-structured
        generator (chunked — peak extra memory is one chunk of windows) and
        sample ``seen_per_user`` seen bits per user inside their bucket."""
        rng = np.random.default_rng(seed)
        I, cap = len(synth.s_user), index.cap
        J, K = synth.B1.shape
        slab = np.empty((I, cap, K), np.float32)
        seen_w = np.zeros((I, cap), np.int8)
        counts = np.zeros(J, np.int64)
        for s in range(0, I, chunk_rows):
            e = min(s + chunk_rows, I)
            rows = np.arange(s, e)
            cand = index.bucket_items[index.user_bucket[rows]]
            slab[s:e] = synth.item_rows(rows, cand)
            size = index.bucket_size[index.user_bucket[rows]]
            if seen_per_user > 0:
                # sample positions within each user's real bucket extent
                pos = np.floor(rng.random((e - s, seen_per_user))
                               * np.maximum(size, 1)[:, None]).astype(np.int64)
                has = size > 0
                seen_w[np.repeat(rows, seen_per_user)[np.repeat(has, seen_per_user)],
                       pos[has].ravel()] = 1
                # counts from the SET bits (not the raw samples, which can
                # collide within a user): item_counts stays consistent with
                # the seen mask, sum(counts) == sum(seen)
                ri, ci = np.nonzero(seen_w[s:e])
                np.add.at(counts, cand[ri, ci], 1)
        return cls(U=synth.U, slab=slab, seen=seen_w, index=index,
                   cold=np.zeros(I, bool), item_counts=counts)

    # --------------------------------------------------------- quantization
    def quantize_int8(self, chunk_rows: int = 131072) -> None:
        """Per-user symmetric int8: scale_i = max|slab_i| / 127 (floored at
        a tiny eps so all-zero rows stay exact), codes = rint(v / scale)
        clipped to ±127 — elementwise error ≤ scale/2."""
        I, cap, K = self.slab.shape
        codes = np.empty((I, cap, K), np.int8)
        scale = np.empty(I, np.float32)
        for s in range(0, I, chunk_rows):
            e = min(s + chunk_rows, I)
            amax = np.abs(self.slab[s:e]).max(axis=(1, 2))
            sc = np.maximum(amax / 127.0, 1e-12).astype(np.float32)
            codes[s:e] = np.clip(
                np.rint(self.slab[s:e] / sc[:, None, None]),
                -127, 127).astype(np.int8)
            scale[s:e] = sc
        self.q_codes, self.q_scale = codes, scale

    def quantize_bf16(self) -> None:
        self.slab_bf16 = self.slab.astype(jnp.bfloat16)

    def int8_score_bound(self, users: np.ndarray) -> np.ndarray:
        """Per-request analytic |Δscore| bound: ||u||₁ · scale/2."""
        assert self.q_scale is not None, "quantize_int8 first"
        users = np.asarray(users)
        return (np.abs(self.U[users]).sum(axis=1)
                * self.q_scale[users] * 0.5).astype(np.float64)

    def bf16_score_bound(self, users: np.ndarray) -> np.ndarray:
        """Per-request analytic |Δscore| bound: max_c Σ_k |u_k·v_kc| · 2⁻⁸."""
        users = np.asarray(users)
        u = np.abs(self.U[users])                          # (n, K)
        w = np.abs(self.slab[users])                       # (n, cap, K)
        return ((w * u[:, None, :]).sum(axis=2).max(axis=1)
                * _BF16_EPS).astype(np.float64)

    # ---------------------------------------------------------- row sharding
    def shard_rows(self, n_shards: int) -> list[tuple[int, "TiledFactorStore"]]:
        """Host-level row sharding: numpy VIEWS of the slabs per shard (no
        copy), user buckets rebased to shard-local rows. Returns
        [(row_start, shard_store), ...] along `sharding.dmf`'s ceil-div row
        layout so routing is ``user // rows_per_shard``."""
        from repro.sharding.dmf import shard_row_slices
        out = []
        for s, e in shard_row_slices(self.n_users, n_shards):
            idx = dataclasses.replace(
                self.index, user_bucket=self.index.user_bucket[s:e])
            out.append((s, TiledFactorStore(
                U=self.U[s:e], slab=self.slab[s:e], seen=self.seen[s:e],
                index=idx, cold=self.cold[s:e],
                item_counts=self.item_counts,
                q_codes=None if self.q_codes is None else self.q_codes[s:e],
                q_scale=None if self.q_scale is None else self.q_scale[s:e],
                slab_bf16=(None if self.slab_bf16 is None
                           else self.slab_bf16[s:e]),
            )))
        return out


@functools.partial(jax.jit, static_argnames=("k",))
def _dispatch_windows(U, slab, scale, seen, bucket_items, user_bucket, uids,
                      *, k: int):
    """One fixed-shape microbatch against the device-resident store: gather
    the requests' (R, cap, K) windows out of the slab and run the tiled
    serve kernel — the only per-request arrays ever formed are the windows
    in flight. ``scale`` is the int8 per-user dequant scale, None for fp32
    and bf16."""
    cand = bucket_items[user_bucket[uids]]
    u, vw, sw = U[uids], slab[uids], seen[uids]
    if slab.dtype == jnp.float32:
        return ops.serve_topk_window(u, vw, cand, sw, k)
    sc = (scale[uids] if scale is not None
          else jnp.ones(uids.shape, jnp.float32))
    return ops.serve_topk_window_quant(u, vw, sc, cand, sw, k)


class TiledServingEngine:
    """Microbatched serving straight off a `TiledFactorStore` — the
    million-scale sibling of `ServingEngine`, same `ServingConfig`, same
    `EngineStats`, same graceful degradation (unknown / cold / empty-bucket
    requests get the popularity slate, flagged). ``mode`` picks the factor
    precision: 'fp32' (bitwise identical to `ServingEngine.recommend` built
    on the same factors), 'int8' or 'bf16' (bounded score error, see the
    module docstring). The engine places U, the seen bits, the index and
    the slab of its precision on the device once; requests then gather
    their windows there."""

    def __init__(self, store: TiledFactorStore,
                 cfg: ServingConfig = ServingConfig(), *, mode: str = "fp32"):
        assert mode in ("fp32", "int8", "bf16"), mode
        if mode == "int8" and store.q_codes is None:
            store.quantize_int8()
        if mode == "bf16" and store.slab_bf16 is None:
            store.quantize_bf16()
        assert cfg.prune, "the tiled store IS the pruned candidate path"
        assert cfg.n_shards == 1, "shard via store.shard_rows + one engine each"
        self.store = store
        self.cfg = cfg
        self.mode = mode
        slab, scale = {"fp32": (store.slab, None),
                       "int8": (store.q_codes, store.q_scale),
                       "bf16": (store.slab_bf16, None)}[mode]
        self._dev = dict(
            U=jnp.asarray(store.U), slab=jnp.asarray(slab),
            scale=None if scale is None else jnp.asarray(scale),
            seen=jnp.asarray(store.seen),
            bucket_items=jnp.asarray(store.index.bucket_items),
            user_bucket=jnp.asarray(store.index.user_bucket))
        self.stats = EngineStats()
        self._bucket_empty = (store.index.bucket_items < 0).all(axis=1)
        # popularity fallback slate — same construction as
        # ServingEngine._refresh_popularity (stable argsort, count/max score)
        top = np.argsort(-store.item_counts, kind="stable")
        self._pop_items = top[: cfg.k].astype(np.int32)
        peak = max(int(store.item_counts.max()), 1)
        self._pop_vals = (
            store.item_counts[self._pop_items] / peak).astype(np.float32)

    def _fallback_mask(self, user_ids: np.ndarray) -> np.ndarray:
        uids = np.asarray(user_ids)
        n = self.store.n_users
        unknown = (uids < 0) | (uids >= n)
        safe = np.clip(uids, 0, n - 1)
        return (unknown | self.store.cold[safe]
                | self._bucket_empty[self.store.index.user_bucket[safe]])

    def _dispatch(self, uids: np.ndarray):
        """One fixed-shape microbatch: ids go to the device, slates come
        back."""
        from repro.obs import trace as trace_lib
        with trace_lib.span("tiled.dispatch", mode=self.mode):
            vals, idx = _dispatch_windows(
                **self._dev, uids=jnp.asarray(uids, jnp.int32),
                k=self.cfg.k)
            jax.block_until_ready(idx)
        return np.asarray(vals), np.asarray(idx)

    def recommend(self, user_ids, return_flags: bool = False):
        """Serve a batch of user ids, results in input order — the same
        contract as `ServingEngine.recommend` (fallback slates flagged)."""
        user_ids = np.asarray(user_ids)
        R, k = self.cfg.microbatch, self.cfg.k
        n = len(user_ids)
        if n == 0:
            out = (np.empty((0, k), np.float32), np.empty((0, k), np.int32))
            return out + (np.empty(0, bool),) if return_flags else out
        flags = (self._fallback_mask(user_ids) if self.cfg.fallback
                 else np.zeros(n, bool))
        safe_ids = np.where(flags, 0, user_ids).astype(np.int64)
        vals = np.empty((n, k), np.float32)
        idx = np.empty((n, k), np.int32)
        t_call = time.perf_counter()
        for s in range(0, n, R):
            e = min(s + R, n)
            buf = np.empty(R, np.int64)
            buf[: e - s] = safe_ids[s:e]
            buf[e - s:] = buf[0]   # pad with a real id (results dropped)
            t0 = time.perf_counter()
            v, i = self._dispatch(buf)
            t1 = time.perf_counter()
            vals[s:e] = v[: e - s]
            idx[s:e] = i[: e - s]
            self.stats.dispatch_seconds.append(t1 - t0)
            self.stats.request_seconds.extend([t1 - t_call] * (e - s))
            self.stats.n_dispatches += 1
            self.stats.n_requests += e - s
        if flags.any():
            vals[flags] = self._pop_vals
            idx[flags] = self._pop_items
            self.stats.n_fallbacks += int(flags.sum())
        if return_flags:
            return vals, idx, flags
        return vals, idx

    @property
    def requests_per_sec(self) -> float:
        s = sum(self.stats.dispatch_seconds)
        return self.stats.n_requests / s if s > 0 else float("nan")
