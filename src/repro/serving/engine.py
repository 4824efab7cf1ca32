"""ServingEngine — microbatched, geo-pruned, online-updatable POI serving.

The deployment story of the paper: trained factors live per learner
(u_i, p^i + q^i) and recommendations are computed at the edge. This engine
simulates that fleet in one process the way the paper's own evaluation
mocks decentralized learning — it gathers each learner's *own* factors per
request (never a shared dense score matrix) and returns top-k unseen POIs.

Request path:

1. **Microbatcher** — a stream of user-id requests is grouped into
   fixed-shape batches of ``ServingConfig.microbatch`` (the tail batch is
   padded with a repeated real id, results sliced off). Fixed shapes mean
   exactly one compiled dispatch per microbatch, ever.
2. **Dispatch** — one jitted call: route each request to its home-city
   candidate bucket (`candidates.CandidateIndex`), gather ONLY the
   (R, cap, K) candidate windows out of the HBM-resident factor buffers
   (never a per-request (R, J, K) item slab), and run the tiled Pallas
   serve kernel (`ops.serve_topk_window`: window scores → running top-k,
   streamed in (8, K, 128) VMEM tiles). Per-request cost AND staging are
   O(cap·K), not O(J·K) — the property that lets `serving/store.py` push
   the same dispatch to 1M users × 100k POIs.
3. **Online refresh** — ``ingest()`` streams new check-ins through
   `serving/online.py` (Eq. 9-11 local steps + neighbor-table scatter),
   then patches only the touched rows of the served V = P + Q view and the
   affected rows of the seen-filter. Served factors track live data with
   no retraining and no raw-rating movement.

``prune=False`` switches the dispatch to the dense full-J streaming kernel
(`ops.recommend_topk_peruser`) — same microbatching, no geo pruning — kept
as the measured baseline and the exactness fallback for users whose city
overflows the bucket cap.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dmf
from repro.core import graph as graph_lib
from repro.core import metrics as metrics_lib
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as trace_lib
from repro.serving import online as online_lib
from repro.serving.candidates import CandidateIndex


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    microbatch: int = 64     # R — fixed dispatch shape (requests padded to it)
    k: int = 10              # recommendations per request
    prune: bool = True       # geo-pruned candidate path vs dense full-J
    n_shards: int = 1        # learner-mesh width: >1 serves row-sharded
                             # U/V/seen, one SPMD dispatch per microbatch
                             # of `microbatch` requests PER SHARD
    fallback: bool = True    # graceful degradation: unknown/cold users and
                             # empty candidate buckets get a popularity
                             # slate (flagged) instead of garbage scores


@dataclasses.dataclass
class EngineStats:
    n_requests: int = 0
    n_dispatches: int = 0
    n_refreshes: int = 0
    n_events: int = 0
    n_fallbacks: int = 0
    dispatch_seconds: list[float] = dataclasses.field(default_factory=list)
    # per-REQUEST arrival→completion, one entry per served request. A request
    # that rides the w-th dispatch of a drain pays for every dispatch before
    # it — the lockstep cost per-dispatch numbers hide. This is the one
    # latency definition shared with scheduling/metrics.py.
    request_seconds: list[float] = dataclasses.field(default_factory=list)

    def reset(self) -> None:
        """Zero all counters/latencies (e.g. after warm-up dispatches)."""
        self.__dict__.update(dataclasses.asdict(EngineStats()))

    def latency_percentiles(self, qs=(50, 95, 99)) -> dict[str, float]:
        """Request-level (arrival→completion) latency percentiles —
        delegates to the one definition in `obs.metrics`."""
        return obs_metrics.latency_percentiles(self.request_seconds, qs)

    def dispatch_latency_percentiles(self, qs=(50, 95, 99)) -> dict[str, float]:
        """Per-dispatch wall-time percentiles (diagnostic, NOT per-request)."""
        return obs_metrics.latency_percentiles(self.dispatch_seconds, qs)

    def publish(self, registry=None, prefix: str = "serving") -> None:
        """Mirror the local counters/latency streams into a metrics
        registry (the global one by default). Counters export as gauges —
        this object is the source of truth and may be `reset()`, so the
        registry reflects its current totals rather than re-accumulating.
        Latency streams replace the histogram's series wholesale for the
        same reason."""
        reg = registry if registry is not None else obs_metrics.get_registry()
        for f in ("n_requests", "n_dispatches", "n_refreshes", "n_events",
                  "n_fallbacks"):
            reg.gauge(f"{prefix}_{f}").set(getattr(self, f))
        for nm in ("dispatch_seconds", "request_seconds"):
            h = reg.histogram(f"{prefix}_{nm}")
            h.reset()
            h.observe_many(getattr(self, nm))


@functools.partial(jax.jit, static_argnames=("k",))
def _dispatch_pruned(U, V, seen, bucket_items, user_bucket, uids, *, k: int):
    """One geo-pruned microbatch: candidate-window gather + tiled serve
    kernel, a single compiled dispatch. Only the (R, cap, K) candidate
    windows are staged out of the HBM-resident factor buffer — never the
    (R, J, K) per-request item slab the pre-tiled path copied. The dispatch
    is read-only over the persistent factor buffers, so nothing is donatable
    here; the state-mutating path (online refresh) donates U/P/Q instead."""
    u = U[uids]                                   # (R, K)   own user factor
    cand = bucket_items[user_bucket[uids]]        # (R, cap) home bucket
    safe = jnp.maximum(cand, 0)                   # pad-safe gather
    vw = V[uids[:, None], safe]                   # (R, cap, K) windows only
    sw = seen[uids[:, None], safe]                # (R, cap) window seen bits
    return ops.serve_topk_window(u, vw, cand, sw, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _dispatch_dense(U, V, seen, uids, *, k: int):
    """Dense baseline microbatch: same gather, full-J streaming top-k."""
    return ops.recommend_topk_peruser(
        U[uids], V[uids], seen[uids], k)


@functools.partial(jax.jit, static_argnames=("k", "prune"))
def _dispatch_rows(U, P, Q, seen, bucket_items, user_bucket, uids, *,
                   k: int, prune: bool):
    """Shard-independent microbatch over the raw factor state: gathers the
    requested rows and forms their V = P + Q view on the fly (gather-then-add
    of the same rows is bitwise identical to gathering a precomputed V).
    This is the `serve_microbatch` dispatch — it never touches the sharded
    device views, so one shard's queue can be served without the SPMD
    lockstep over the whole mesh. The pruned path gathers only the
    (R, cap, K) candidate windows straight out of P/Q (gather-then-add of
    the same elements is bitwise identical to windowing a precomputed V)."""
    if prune:
        with jax.named_scope("serve.window_gather"):
            u = U[uids]
            cand = bucket_items[user_bucket[uids]]
            safe = jnp.maximum(cand, 0)
            vw = P[uids[:, None], safe] + Q[uids[:, None], safe]  # (R, cap, K)
            sw = seen[uids[:, None], safe]
        with jax.named_scope("serve.topk"):
            return ops.serve_topk_window(u, vw, cand, sw, k)
    u = U[uids]
    v = P[uids] + Q[uids]
    s = seen[uids]
    return ops.recommend_topk_peruser(u, v, s, k)


def _make_sharded_dispatch(mesh, *, k: int, prune: bool):
    """SPMD serve dispatch over the ``learners`` mesh: every shard gathers
    its OWN users' (u_i, v^i, seen_i) rows and runs the same fused serve
    kernel (or the dense streaming kernel) on its local microbatch — one
    compiled dispatch serves mesh-width × microbatch requests. ``uids`` are
    shard-LOCAL row ids shaped (n_shards, R); the candidate buckets are
    replicated (items are global ids everywhere)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.dmf import AXIS

    def body(U, V, seen, user_bucket, bucket_items, uids):
        u_l = uids[0]                        # (R,) local row ids
        u = U[u_l]
        if prune:
            cand = bucket_items[user_bucket[u_l]]
            safe = jnp.maximum(cand, 0)
            vw = V[u_l[:, None], safe]       # (R, cap, K) windows only
            sw = seen[u_l[:, None], safe]
            return ops.serve_topk_window(u, vw, cand, sw, k)
        return ops.recommend_topk_peruser(u, V[u_l], seen[u_l], k)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(None, None), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
        check_vma=False,
    ))


class ServingEngine:
    """Batched POI recommendation over a trained `DMFState`.

    ``nbr`` + ``dmf_cfg`` are only required for `ingest()` (online refresh).

    The engine owns a private copy of the factor state: `ingest()` donates
    its U/P/Q buffers to the refresh step (in-place at the XLA level), and
    copying once at construction keeps that from invalidating the
    caller's trained state (e.g. a `FitResult` still used for evaluation).
    """

    def __init__(
        self,
        state: dmf.DMFState,
        index: CandidateIndex,
        cfg: ServingConfig = ServingConfig(),
        *,
        train: np.ndarray | None = None,
        seen: np.ndarray | None = None,
        nbr: graph_lib.NeighborTable | None = None,
        dmf_cfg: dmf.DMFConfig | None = None,
    ):
        self.state = dmf.DMFState(
            U=jnp.array(state.U), P=jnp.array(state.P), Q=jnp.array(state.Q))
        self.index = index
        self.cfg = cfg
        self.nbr = nbr
        self.dmf_cfg = dmf_cfg
        I, J = state.P.shape[0], state.P.shape[1]
        assert index.n_items == J, (index.n_items, J)
        if seen is None:
            assert train is not None, "need `train` pairs or a `seen` mask"
            seen = metrics_lib.masks_from_interactions(I, J, train)
        seen_np = np.asarray(seen).astype(bool)
        self.seen = jnp.asarray(seen_np.astype(np.int8))
        self._bucket_items = jnp.asarray(index.bucket_items)
        self._user_bucket = jnp.asarray(index.user_bucket)
        # graceful-degradation state (host-side, cheap): which requests
        # cannot be served from learned factors — unknown ids, cold-start
        # users (no interactions => their zero-init item factors score
        # garbage), users whose home-city candidate bucket is empty — and
        # the popularity-ranked slate they get instead (check-in counts
        # from the seen-filter, kept fresh by `ingest`).
        self._n_users = I
        self._cold = ~seen_np.any(axis=1)
        self._item_counts = seen_np.sum(axis=0).astype(np.int64)
        self._user_bucket_np = np.asarray(index.user_bucket)
        self._bucket_empty = (np.asarray(index.bucket_items) < 0).all(axis=1)
        self._refresh_popularity()
        self._sharded = cfg.n_shards > 1
        if self._sharded:
            # learner-sharded serving: the served views live row-sharded on
            # the mesh (the sharded V REPLACES the single-device V = P + Q
            # view — keeping both would double the engine's largest buffer);
            # each SPMD dispatch serves `microbatch` requests per shard,
            # each shard reading only its own users' rows.
            from jax.sharding import NamedSharding, PartitionSpec as PSpec

            from repro.sharding import dmf as sharded_dmf

            self._mesh = sharded_dmf.make_learner_mesh(cfg.n_shards)
            self._rows = sharded_dmf.rows_per_shard(I, cfg.n_shards)
            I_pad = self._rows * cfg.n_shards
            sh = NamedSharding(self._mesh, PSpec(sharded_dmf.AXIS))
            pad = sharded_dmf.pad_rows
            self._U_sh = jax.device_put(pad(self.state.U, I_pad), sh)
            self._V_sh = jax.device_put(
                pad(self.state.P + self.state.Q, I_pad), sh)
            self._seen_sh = jax.device_put(pad(self.seen, I_pad), sh)
            self._ub_sh = jax.device_put(pad(self._user_bucket, I_pad), sh)
            self._dispatch_sh = _make_sharded_dispatch(
                self._mesh, k=cfg.k, prune=cfg.prune)
        else:
            self.V = state.P + state.Q            # served per-learner view
        # persistent stream: successive ingest() calls must draw *fresh*
        # negatives, not replay the same ones (which would keep hammering
        # the same arbitrary items' scores down)
        self._rng = np.random.default_rng(
            dmf_cfg.seed if dmf_cfg is not None else 0)
        self.stats = EngineStats()

    # -------------------------------------------------------------- fallback
    def _refresh_popularity(self) -> None:
        """Rebuild the popularity slate: top-k items by check-in count,
        values = count / max count (a [0,1] pseudo-score, deliberately NOT
        on the factor-score scale — fallback responses are flagged)."""
        top = np.argsort(-self._item_counts, kind="stable")
        self._pop_items = top[: self.cfg.k].astype(np.int32)
        peak = max(int(self._item_counts.max()), 1)
        self._pop_vals = (
            self._item_counts[self._pop_items] / peak).astype(np.float32)

    def _fallback_mask(self, user_ids: np.ndarray) -> np.ndarray:
        """Per-request bool mask: True where the learned-factor path cannot
        produce a meaningful slate and the popularity fallback applies."""
        uids = np.asarray(user_ids)
        unknown = (uids < 0) | (uids >= self._n_users)
        safe = np.clip(uids, 0, self._n_users - 1)
        flags = unknown | self._cold[safe]
        if self.cfg.prune:
            flags = flags | self._bucket_empty[self._user_bucket_np[safe]]
        return flags

    # ------------------------------------------------------------------ serve
    def _microbatches(
        self, user_ids: Iterable[int], t_arrival: float | None = None
    ) -> Iterator[tuple[np.ndarray, int, np.ndarray]]:
        """Fixed-shape request batches: (padded ids (R,), n_real, arrival
        timestamps (n_real,) — stamped when each id was pulled from the
        stream, the request-level latency anchor). ``t_arrival`` overrides
        the pull-time stamps with one shared anchor — `recommend` passes its
        call time, because there the whole batch is queued up-front and later
        microbatches wait on the earlier ones."""
        R = self.cfg.microbatch
        buf = np.zeros(R, np.int32)
        arr = np.zeros(R, np.float64)
        n = 0
        for uid in user_ids:
            buf[n] = uid
            arr[n] = time.perf_counter() if t_arrival is None else t_arrival
            n += 1
            if n == R:
                yield buf.copy(), n, arr[:n].copy()
                n = 0
        if n:
            buf[n:] = buf[0]       # pad with a real user id (results dropped)
            yield buf.copy(), n, arr[:n].copy()

    # ------------------------------------------------------------ sharded serve
    def serve_wave(self, uids_local: np.ndarray) -> tuple[np.ndarray, np.ndarray, float]:
        """ONE lockstep SPMD dispatch over the whole mesh: ``uids_local`` is
        (n_shards, microbatch) shard-LOCAL row ids (pad unused slots with 0 —
        callers drop those results). Every shard computes its full microbatch
        whether its queue was full or empty; returns
        (vals (D, R, k), idx (D, R, k), wall seconds). This is the global-
        batch primitive the continuous-batching scheduler's per-shard
        independent dispatch (`serve_microbatch`) is measured against."""
        D, R, k = self.cfg.n_shards, self.cfg.microbatch, self.cfg.k
        t0 = time.perf_counter()
        with trace_lib.span("engine.serve_wave", shards=D, microbatch=R):
            vals, idx = self._dispatch_sh(
                self._U_sh, self._V_sh, self._seen_sh, self._ub_sh,
                self._bucket_items, jnp.asarray(uids_local))
            jax.block_until_ready(idx)
        dt = time.perf_counter() - t0
        self.stats.dispatch_seconds.append(dt)
        self.stats.n_dispatches += 1
        return (np.asarray(vals).reshape(D, R, k),
                np.asarray(idx).reshape(D, R, k), dt)

    def _sharded_dispatches(
        self, user_ids: np.ndarray
    ) -> Iterator[tuple[list[np.ndarray], np.ndarray, np.ndarray]]:
        """Route requests to their user's home shard and drain the per-shard
        queues SPMD: each dispatch takes up to `microbatch` requests from
        EVERY shard's queue at once (uids rebased to shard-local rows,
        padding = local row 0, results dropped). Yields
        (positions-per-shard, vals (D, R, k), idx (D, R, k)).

        Request-level latency: every request in the drain "arrived" when the
        drain started, so a request served by the w-th dispatch is charged
        the full wall time of dispatches 1..w — the lockstep queueing cost.
        """
        D, R = self.cfg.n_shards, self.cfg.microbatch
        shard = user_ids // self._rows
        queues = [np.nonzero(shard == d)[0] for d in range(D)]
        offs = [0] * D
        t_arrival = time.perf_counter()
        while any(o < len(q) for o, q in zip(offs, queues)):
            uids_l = np.zeros((D, R), np.int32)
            sel = []
            for d in range(D):
                take = queues[d][offs[d] : offs[d] + R]
                offs[d] += len(take)
                uids_l[d, : len(take)] = user_ids[take] % self._rows
                sel.append(take)
            vals, idx, _ = self.serve_wave(uids_l)
            n_real = int(sum(len(t) for t in sel))
            self.stats.n_requests += n_real
            self.stats.request_seconds.extend(
                [time.perf_counter() - t_arrival] * n_real)
            yield sel, vals, idx

    def _serve_sharded(self, user_ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Serve a whole batch SPMD, results in the caller's request order."""
        k = self.cfg.k
        out_v = np.zeros((len(user_ids), k), np.float32)
        out_i = np.full((len(user_ids), k), -1, np.int32)
        for sel, vals, idx in self._sharded_dispatches(user_ids):
            for d, take in enumerate(sel):
                if len(take):
                    out_v[take] = vals[d, : len(take)]
                    out_i[take] = idx[d, : len(take)]
        return out_v, out_i

    def serve_stream(
        self, user_ids: Iterable[int], ordered: bool = False,
        _t_arrival: float | None = None,
    ) -> Iterator[tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Drain a request stream; yields (user_ids, vals, idx) per
        microbatch — one jitted dispatch each, padding sliced off.

        In sharded mode (``n_shards > 1``) the stream is drained up-front,
        requests route to their home shard, and each yield is one SPMD
        dispatch covering up to `microbatch` requests per shard. By default
        the yield order follows the shard queues, not strict arrival order;
        ``ordered=True`` reassembles results by arrival index and yields the
        maximal arrival-contiguous prefix after each dispatch (same
        dispatches, results buffered — first yields may be delayed until the
        slowest-filling shard completes the requests ahead of them). The
        non-sharded path is always in arrival order."""
        if self._sharded:
            ids = np.asarray(list(user_ids), np.int64)
            if not ordered:
                for sel, vals, idx in self._sharded_dispatches(ids):
                    pos = np.concatenate([t for t in sel if len(t)])
                    v = np.concatenate(
                        [vals[d, : len(t)] for d, t in enumerate(sel) if len(t)])
                    i = np.concatenate(
                        [idx[d, : len(t)] for d, t in enumerate(sel) if len(t)])
                    yield ids[pos], v, i
                return
            n_total, k = len(ids), self.cfg.k
            out_v = np.zeros((n_total, k), np.float32)
            out_i = np.full((n_total, k), -1, np.int32)
            done = np.zeros(n_total, bool)
            emitted = 0
            for sel, vals, idx in self._sharded_dispatches(ids):
                for d, take in enumerate(sel):
                    if len(take):
                        out_v[take] = vals[d, : len(take)]
                        out_i[take] = idx[d, : len(take)]
                        done[take] = True
                stop = emitted
                while stop < n_total and done[stop]:
                    stop += 1
                if stop > emitted:
                    yield ids[emitted:stop], out_v[emitted:stop], out_i[emitted:stop]
                    emitted = stop
            assert emitted == n_total, "sharded drain left requests unserved"
            return
        for buf, n, arr in self._microbatches(user_ids, _t_arrival):
            uids = jnp.asarray(buf)
            t0 = time.perf_counter()
            with trace_lib.span("engine.dispatch", n_real=n,
                                prune=self.cfg.prune):
                if self.cfg.prune:
                    vals, idx = _dispatch_pruned(
                        self.state.U, self.V, self.seen,
                        self._bucket_items, self._user_bucket, uids,
                        k=self.cfg.k)
                else:
                    vals, idx = _dispatch_dense(
                        self.state.U, self.V, self.seen, uids, k=self.cfg.k)
                jax.block_until_ready(idx)
            t1 = time.perf_counter()
            self.stats.dispatch_seconds.append(t1 - t0)
            self.stats.n_dispatches += 1
            self.stats.n_requests += n
            self.stats.request_seconds.extend((t1 - arr).tolist())
            yield buf[:n], np.asarray(vals)[:n], np.asarray(idx)[:n]

    def serve_microbatch(self, user_ids, return_flags: bool = False):
        """Per-shard INDEPENDENT dispatch primitive: serve ≤ `microbatch`
        requests in one jitted call over the raw factor state, with no SPMD
        lockstep across the mesh — this is what `scheduling.Scheduler` calls
        per shard queue, so one slow or empty queue never holds a global
        batch hostage. Works at any ``n_shards`` (the dispatch reads the
        unsharded state copy the engine keeps for ingest) and is bitwise
        identical per request to `recommend` / the SPMD path: same serve
        kernel, same rows, per-row independent.

        Returns ``(vals (n,k), idx (n,k), service_seconds)`` — plus the
        per-request fallback flags before the seconds if ``return_flags``.
        Fallback handling matches `recommend`: flagged requests (unknown /
        cold / empty-bucket users) are clamped pre-dispatch and overwritten
        with the popularity slate."""
        user_ids = np.asarray(user_ids)
        n, R, k = len(user_ids), self.cfg.microbatch, self.cfg.k
        assert n <= R, f"serve_microbatch takes ≤ microbatch ids ({n} > {R})"
        if n == 0:
            out = (np.empty((0, k), np.float32), np.empty((0, k), np.int32))
            return out + ((np.empty(0, bool),) if return_flags else ()) + (0.0,)
        with trace_lib.span("engine.serve_microbatch", n_real=n):
            with trace_lib.span("serve.prepare"):
                flags = (self._fallback_mask(user_ids) if self.cfg.fallback
                         else np.zeros(n, bool))
                buf = np.zeros(R, np.int32)
                buf[:n] = np.where(flags, 0, user_ids)
                buf[n:] = buf[0]   # pad with a real user id (results dropped)
            t0 = time.perf_counter()
            with trace_lib.span("serve.launch"):
                vals, idx = _dispatch_rows(
                    self.state.U, self.state.P, self.state.Q, self.seen,
                    self._bucket_items, self._user_bucket, jnp.asarray(buf),
                    k=k, prune=self.cfg.prune)
            with trace_lib.span("serve.device_wait"):
                jax.block_until_ready(idx)
            dt = time.perf_counter() - t0
            self.stats.dispatch_seconds.append(dt)
            self.stats.request_seconds.extend([dt] * n)
            self.stats.n_dispatches += 1
            self.stats.n_requests += n
            with trace_lib.span("serve.fetch"):
                vals = np.array(np.asarray(vals)[:n])
                idx = np.array(np.asarray(idx)[:n])
            with trace_lib.span("serve.fallback"):
                if flags.any():
                    vals[flags] = self._pop_vals
                    idx[flags] = self._pop_items
                    self.stats.n_fallbacks += int(flags.sum())
        if return_flags:
            return vals, idx, flags, dt
        return vals, idx, dt

    def recommend(self, user_ids, return_flags: bool = False):
        """Convenience: serve a whole batch of user ids, results aligned to
        the input order (also in sharded mode).

        Graceful degradation (``cfg.fallback``, on by default): requests the
        factor path cannot serve — unknown ids, cold-start users, empty
        candidate buckets — return the popularity slate instead of garbage;
        their ids are clamped to row 0 before dispatch (essential in
        sharded mode, where an out-of-range id would route to no shard) and
        the dispatched rows are overwritten. ``return_flags=True`` appends
        the per-request fallback bool mask to the result."""
        user_ids = np.asarray(user_ids)
        k = self.cfg.k
        if len(user_ids) == 0:
            out = (np.empty((0, k), np.float32), np.empty((0, k), np.int32))
            return out + (np.empty(0, bool),) if return_flags else out
        flags = (self._fallback_mask(user_ids) if self.cfg.fallback
                 else np.zeros(len(user_ids), bool))
        safe_ids = np.where(flags, 0, user_ids)
        if self._sharded:
            vals, idx = self._serve_sharded(safe_ids.astype(np.int64))
        else:
            vals, idx = [], []
            t_call = time.perf_counter()
            for _, v, i in self.serve_stream(
                    (int(u) for u in safe_ids), _t_arrival=t_call):
                vals.append(v)
                idx.append(i)
            vals, idx = np.concatenate(vals), np.concatenate(idx)
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

    # ----------------------------------------------------------------- ingest
    def ingest(
        self,
        events: np.ndarray,
        ocfg: online_lib.OnlineConfig = online_lib.OnlineConfig(),
        rng: np.random.Generator | None = None,
    ) -> online_lib.RefreshReport:
        """Stream new check-ins through the online refresh and patch the
        served state: U/P/Q via Eq. 9-11 + neighbor scatter, the V = P + Q
        view only on touched rows, the seen-filter only on affected rows
        (the new check-ins drop out of those users' candidate sets)."""
        assert self.nbr is not None and self.dmf_cfg is not None, (
            "engine built without nbr/dmf_cfg — online refresh unavailable")
        events = np.asarray(events)
        with trace_lib.span("engine.ingest", n_events=len(events)):
            self.state, report = online_lib.online_refresh(
                self.state, self.nbr, events, self.dmf_cfg, ocfg,
                rng if rng is not None else self._rng)
        if not self._sharded and len(report.touched_users):
            t = jnp.asarray(report.touched_users)
            self.V = self.V.at[t].set(self.state.P[t] + self.state.Q[t])
        if len(events):
            self.seen = self.seen.at[events[:, 0], events[:, 1]].set(1)
        if self._sharded:
            # apply the row patches to the sharded served views (global
            # row ids are unchanged by padding — the pad sits at the end)
            if len(report.touched_users):
                t = jnp.asarray(report.touched_users)
                self._V_sh = self._V_sh.at[t].set(
                    self.state.P[t] + self.state.Q[t])
            if len(report.affected_users):
                a = jnp.asarray(report.affected_users)
                self._U_sh = self._U_sh.at[a].set(self.state.U[a])
            if len(events):
                self._seen_sh = self._seen_sh.at[
                    events[:, 0], events[:, 1]].set(1)
        if len(events):
            # keep the degradation state fresh: a user with a first
            # check-in stops being cold, and popularity tracks the stream
            np.add.at(self._item_counts, events[:, 1].astype(np.int64), 1)
            self._cold[events[:, 0].astype(np.int64)] = False
            self._refresh_popularity()
        self.stats.n_refreshes += 1
        self.stats.n_events += int(len(events))
        return report
