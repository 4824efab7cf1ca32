"""A miniature benchmark root for the CPU tests: the cells' files at a
size a test run holds (120 users, 96 POIs, 4 cities), with the repository's
metric readers and program, so that the harness runs end to end here
without a chip."""
from __future__ import annotations

import io
import json
import pathlib
import shutil
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
PKG = REPO / "perfbench"

PENDING = PKG / "tests" / "pending.json"

TINY_DATA = {"n_users": 120, "n_items": 96, "n_ratings": 900,
             "n_cities": 4, "seed": 0}


def tiny_config(name: str) -> dict:
    cfg = json.loads((PKG / "configs" / f"{name}.json").read_text())
    cfg["data"] = dict(TINY_DATA)
    return cfg


def tiny_traffic(name: str) -> dict:
    trf = json.loads((PKG / "traffic" / f"{name}.json").read_text())
    if trf["kind"] == "train":
        trf["epochs_per_job"] = 4
    else:
        trf["arrivals"] = dict(trf["arrivals"], rate_rps=60.0)
        trf["warm_dispatches"] = 1
        trf["check_requests"] = 64
        trf["microbatch"] = 8
    return trf


def with_pending(bench: dict) -> dict:
    """``bench`` with the entries of `pending.json`: the cells whose files
    are here but whose rates, limits and bounds wait for their runs on the
    chip. A metric that both name reports in the cells of both."""
    pend = json.loads(PENDING.read_text())
    out = dict(bench)
    for sec in ("configs", "workloads"):
        out[sec] = bench[sec] + pend[sec]
    for sec in ("end_to_end", "per_layer"):
        by = {m["name"]: dict(m) for m in bench[sec]}
        for m in pend[sec]:
            if m["name"] in by:
                by[m["name"]]["workloads"] = (by[m["name"]]["workloads"]
                                              + m["workloads"])
            else:
                by[m["name"]] = m
        out[sec] = list(by.values())
    return out


def make_root(tmp: pathlib.Path, cells=None) -> pathlib.Path:
    """BENCHMARK.json with the repository's cells (or those named in
    ``cells``, which may be pending ones) over the tiny files, the metric
    readers, the peak table and a link to src/."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    if cells is None:
        cells = [w["name"] for w in bench["workloads"]]
    bench = with_pending(bench)
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] in cells]
    base = tmp / "perfbench"
    for d in ("configs", "traffic", "limits"):
        (base / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(PKG / "metrics", base / "metrics")
    for w in bench["workloads"]:
        trf = tiny_traffic(w["traffic"])
        (base / "configs" / f"{w['config']}.json").write_text(
            json.dumps(tiny_config(w["config"])))
        (base / "traffic" / f"{w['traffic']}.json").write_text(json.dumps(trf))
        shutil.copy(PKG / "limits" / f"{w['name']}.json",
                    base / "limits" / f"{w['name']}.json")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "src").symlink_to(REPO / "src")
    return tmp


def run_cell(root: pathlib.Path, cell: str, seed: int = 7,
             seconds: float = 1.0, trace: int = 0):
    """One harness run here, the look for a chip skipped; returns the exit
    code, the result object (or None) and stderr."""
    from perfbench import harness
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(root, ["--workload", cell, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", str(trace)],
                     time.perf_counter(), require_tpu=False, cache=False,
                     out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
