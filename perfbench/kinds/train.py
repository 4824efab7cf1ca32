"""Training traffic: back-to-back jobs through `repro.core.dmf.fit`, as a
user runs them, each from a fresh `init_state` with its own seed.

Set-up builds the data, the walk graph and the configuration, then runs
the first `COMPARED_EPOCHS` epochs of job 0 through the same `fit` call the
window makes. They compile and warm every program (each epoch runs the
same one), and are what `correct` compares: the callback reads the leaf
norms of the change after the first epoch, and after the last compared
epoch copies the state to the host. The window then runs jobs 1, 2, ...
and closes at the end of the first epoch that ends after ``seconds``: an
epoch on the chip takes seconds, a whole job minutes.
"""
from __future__ import annotations

import math
import time

import numpy as np

from perfbench import checks, data
from perfbench.refs import dmf as ref

COMPARED_EPOCHS = 3


class _WindowClosed(Exception):
    """Raised after the epoch that ends the window, to leave its job."""


def job_seed(seed: int, job: int) -> int:
    return int(np.random.SeedSequence([seed % 2**64, job]).generate_state(
        1, np.uint64)[0])


class Session:
    def __init__(self, cell, seed: int, seconds: float):
        import jax

        from repro.core import dmf, graph
        self.cell, self.seed = cell, seed
        cfg, trf = cell.config, cell.traffic
        self.ds = data.from_config(cfg)
        g = cfg["graph"]
        gcfg = graph.GraphConfig(
            n_neighbors=g["n_neighbors"], walk_length=g["walk_length"],
            hop_damping=g["hop_damping"], uniform_weights=g["uniform_weights"])
        W = graph.build_adjacency(self.ds.user_coords, self.ds.user_city, gcfg)
        self.nbr = graph.walk_neighbor_table(W, gcfg)
        del W
        m, dp = cfg["model"], trf.get("dp") or {}
        self.dcfg = dmf.DMFConfig(
            n_users=self.ds.n_users, n_items=self.ds.n_items, dim=m["dim"],
            alpha=m["alpha"], beta=m["beta"], gamma=m["gamma"], lr=m["lr"],
            neg_samples=m["neg_samples"], batch_size=m["batch_size"],
            init_scale=m["init_scale"],
            dp_clip=float(dp.get("clip", math.inf)),
            dp_sigma=float(dp.get("sigma", 0.0)),
            dp_seed=int(dp.get("dp_seed", 0)))
        self.dp_delta = float(dp.get("delta", 1e-5))
        self.epochs = int(trf["epochs_per_job"])
        self.compared = min(COMPARED_EPOCHS, self.epochs)
        n_pos = len(self.ds.train) * (1 + m["neg_samples"])
        self.events_per_epoch = (n_pos // m["batch_size"]) * m["batch_size"]
        self._fit = dmf.fit
        self._jax = jax
        self._M = None
        self.probe = self._probe_job(job_seed(seed, 0))

    def _run_job(self, js: int, callback, epochs: int | None = None):
        return self._fit(self.dcfg, self.ds.train, self.nbr,
                         epochs=epochs or self.epochs, seed=js,
                         dp_delta=self.dp_delta, callback=callback)

    def _probe_job(self, js: int) -> dict:
        jnp = self._jax.numpy
        _, U0 = ref.Trainer(self.cell.config, self.cell.traffic, self.ds,
                            None).init_users(js)
        U0d = jnp.asarray(U0)
        rec = {"job_seed": js}

        def cb(t, state, loss):
            if t == 0:
                rec["d1"] = np.asarray(
                    ref.leaf_norms(state.U, state.P, state.Q, U0d), np.float64)
            if t == self.compared - 1:
                rec["d_end"] = np.asarray(
                    ref.leaf_norms(state.U, state.P, state.Q, U0d), np.float64)
                rec["state"] = self._jax.device_get((state.U, state.P, state.Q))

        res = self._run_job(js, cb, epochs=self.compared)
        rec["losses"] = list(res.train_losses[:self.compared])
        del res
        return rec

    def window(self, seconds: float, annotate) -> dict:
        losses = []
        t0 = None

        def cb(t, state, loss):
            losses.append(loss)         # the epoch's loss is on the host
            if time.perf_counter() - t0 >= seconds:
                raise _WindowClosed

        jobs = 0
        with annotate():
            t0 = time.perf_counter()
            try:
                while True:
                    with self._jax.profiler.TraceAnnotation("perfbench.job"):
                        self._run_job(job_seed(self.seed, 1 + jobs), cb)
                    jobs += 1
            except _WindowClosed:
                pass
            t1 = time.perf_counter()
        return {"window_s": t1 - t0, "jobs": jobs, "epochs": len(losses),
                "events": len(losses) * self.events_per_epoch,
                "nonfinite": int(sum(not np.isfinite(x) for x in losses))}

    def end_to_end(self, rec: dict) -> dict:
        return {"train_events_per_s": rec["events"] / rec["window_s"]}

    def layer_inputs(self, rec: dict) -> dict:
        """What the per-layer readers count from: the window's record and
        the per-epoch work, with each sender's receiver count taken from
        the reference's own walk matrix."""
        m = self.cell.config["model"]
        fan = ref.fanout(self.walk())
        senders = self.ds.train[:, 0]
        share = self.events_per_epoch / (len(senders) * (1 + m["neg_samples"]))
        return {**rec, "kind": "train", "dim": m["dim"],
                "dp": bool(self.cell.traffic.get("dp")),
                "events_per_epoch": self.events_per_epoch,
                "receivers_per_epoch":
                    float(fan[senders].sum() * (1 + m["neg_samples"]) * share)}

    def walk(self) -> np.ndarray:
        if self._M is None:
            self._M = ref.walk_for(self.cell.config, self.ds)
        return self._M

    def release(self) -> None:
        pass     # each job's state is dropped when `fit` returns

    def check(self, rec: dict):
        import gc
        jnp = self._jax.numpy
        gc.collect()
        r = ref.Trainer(self.cell.config, self.cell.traffic, self.ds,
                        self.walk()).run(
            self.probe["job_seed"], self.compared)
        prog_state = [jnp.asarray(x) for x in self.probe.pop("state")]
        dists = np.asarray(ref.leaf_dists(*r["state"], *prog_state), np.float64)
        del prog_state, r["state"]
        numbers = checks.train_numbers(self.probe, r, dists)
        return numbers, rec["epochs"], rec["nonfinite"]
