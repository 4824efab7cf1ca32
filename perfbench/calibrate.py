"""Measurements that set the benchmark's numbers, run by hand on the chip;
the benchmark's own runs never run them.

    python3 perfbench/calibrate.py knee --workload <serving cell> \\
        --rates 10000,20000 --seconds 4
        # one set-up, open-loop Poisson at each rate: p95, share completed;
        # the knee is the highest rate with p95 <= --slo-ms and >= 99% done
    python3 perfbench/calibrate.py readings --workload <cell> \\
        --seeds 11,12,13 [--seconds 2]
        # per seed, the correctness numbers of the program (the lower
        # readings) and of the control: the reference in bfloat16 put in
        # the program's place (the upper readings)

Each line printed is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def knee(cell, seed: int, rates, seconds: float, slo_ms: float) -> None:
    """The rates in increasing order, until two in a row miss the limit."""
    from perfbench.kinds import serve
    sess = serve.Session(cell, seed, seconds)
    rng = np.random.default_rng(seed + 1)
    misses = 0
    for rate in sorted(rates):
        if misses == 2:
            break
        trf = dict(cell.traffic, arrivals={"process": "poisson",
                                          "rate_rps": float(rate)})
        sess.load(trf, seconds, rng)
        rec = sess.window(seconds)
        e2e = sess.end_to_end(rec)
        done = e2e["serve_rps"] * seconds / max(len(rec["times"]), 1)
        d = rec["dispatches"]
        meets = bool(e2e["serve_p95_ms"] <= slo_ms and done >= 0.99)
        misses = 0 if meets else misses + 1
        print(json.dumps({
            "rate_rps": rate, "offered": len(rec["times"]),
            "p95_ms": e2e["serve_p95_ms"], "completed_share": done,
            "dispatches": len(d), "mean_batch": float(d[:, 2].mean()),
            "dispatch_ms_median": 1e3 * float(np.median(d[:, 1] - d[:, 0])),
            "meets_slo": meets}), flush=True)


def train_readings(cell, seeds) -> None:
    import jax.numpy as jnp

    from perfbench import checks
    from perfbench.kinds import train
    from perfbench.refs import dmf as ref
    sess = train.Session(cell, seeds[0], 0.0)
    for s in seeds:
        t0 = time.perf_counter()
        if s != seeds[0]:
            sess.seed = s
            sess.probe = sess._probe_job(train.job_seed(s, 0))
        js = sess.probe["job_seed"]
        t1 = time.perf_counter()
        r32 = ref.Trainer(cell.config, cell.traffic, sess.ds, sess.walk()).run(
            js, sess.compared)
        t2 = time.perf_counter()
        prog_state = [jnp.asarray(x) for x in sess.probe.pop("state")]
        prog = checks.train_numbers(sess.probe, r32, np.asarray(
            ref.leaf_dists(*r32["state"], *prog_state), np.float64))
        del prog_state
        r16 = ref.Trainer(cell.config, cell.traffic, sess.ds, sess.walk(),
                          dtype=jnp.bfloat16).run(js, sess.compared)
        ctrl = checks.train_numbers(r16, r32, np.asarray(
            ref.leaf_dists(*r32["state"], *r16["state"]), np.float64))
        del r16
        faults = {}
        for f in (ref.Trainer.FAULTS if s in seeds[:3] else ()):
            rf = ref.Trainer(cell.config, cell.traffic, sess.ds, sess.walk(),
                             fault=f).run(js, sess.compared)
            faults[f] = checks.train_numbers(rf, r32, np.asarray(
                ref.leaf_dists(*r32["state"], *rf["state"]), np.float64))
            del rf
        del r32
        print(json.dumps({"seed": s, "program": prog, "control": ctrl,
                          "faults": faults, "probe_s": t1 - t0,
                          "reference_s": t2 - t1}), flush=True)


def serve_readings(cell, seeds, seconds: float) -> None:
    from perfbench.kinds import serve
    for s in seeds:
        t0 = time.perf_counter()
        sess = serve.Session(cell, s, seconds)
        rec = sess.window(seconds)
        sess.release()
        t1 = time.perf_counter()
        prog, attempted, failed = sess.check(rec)
        t2 = time.perf_counter()
        pick = sess.sample(rec)
        ctrl = serve.compare(sess.ds, sess.shape, s, rec["users"][pick], None,
                             None, sess.k, dtype="bfloat16")
        print(json.dumps({"seed": s, "program": prog, "control": ctrl,
                          "attempted": attempted, "failed": failed,
                          "window_and_setup_s": t1 - t0,
                          "reference_s": t2 - t1}), flush=True)
        del sess


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("knee", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--slo-ms", type=float, default=50.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from perfbench import harness
    cell = harness.find_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    print(json.dumps({"device": harness.device_info(cell.chips)}), flush=True)
    seeds = [int(x) for x in args.seeds.split(",")]
    if args.mode == "knee":
        knee(cell, seeds[0], [float(r) for r in args.rates.split(",")],
             args.seconds, args.slo_ms)
    elif cell.traffic["kind"] == "train":
        train_readings(cell, seeds)
    else:
        serve_readings(cell, seeds, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
