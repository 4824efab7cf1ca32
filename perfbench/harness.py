"""One run of one benchmark cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from `BENCHMARK.json` at the checkout's root:
the cell (`workloads`), its configuration (`perfbench/configs/<config>.json`),
its traffic mix (`perfbench/traffic/<traffic>.json`, whose ``kind`` names
the runner in `perfbench/kinds/`), the limits of its correctness numbers
(`perfbench/limits/<cell>.json`), its metrics (`end_to_end`, `per_layer`),
and each per-layer metric's reader (`perfbench/metrics/<metric>.py`, a
`read(inputs)` that returns the number, or None where it finds nothing to
read). A new cell or metric is new files and entries; no file changes.

A run sets up (timed as `setup_s`), measures for ``--seconds`` with the
profiler off (``--trace 0``: the end-to-end metrics) or traces a window of
at most `TRACE_S` (``--trace 1``: the per-layer metrics, `busy_s`,
`window_s` and the breakdown), reads the device's peak memory, frees the
program's state, and then decides `correct` against the plain reference.
The last line of stdout is the result object; the numbers compared, each
beside its limit, are the last lines of stderr and the result's last key.
It exits non-zero, printing no result, where the backend is not a TPU, the
chips are fewer than the cell asks for, or the program is missing.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import pathlib
import sys
import time
import traceback

PKG = pathlib.Path(__file__).resolve().parent
ROOT = PKG.parent
TRACE_S = 4.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class SetupError(RuntimeError):
    """The run cannot start here: no chip, too few chips, no program."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    metrics_dir: pathlib.Path


def _load(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SetupError(f"missing {path}")
    return json.loads(path.read_text())


def find_cell(root: pathlib.Path, name: str) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    wl = by_name[name]
    base = root / PKG.name
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(name=name, chips=int(wl["chips"]),
                config=_load(base / "configs" / f"{wl['config']}.json"),
                traffic=_load(base / "traffic" / f"{wl['traffic']}.json"),
                limits=_load(base / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=layer,
                metrics_dir=base / "metrics")


def load_reader(metrics_dir: pathlib.Path, name: str):
    path = metrics_dir / f"{name}.py"
    if not path.is_file():
        raise SetupError(f"no reader {path} for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at the fixed `.jax_cache` of the checkout,
    unless JAX_COMPILATION_CACHE_DIR names one; every program is kept."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise SetupError(f"no TPU: the backend is {info['platform']}")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, {len(devs)} found")
    return info


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileCounter:
    """Compilations and cache loads while `on` (none belong in a window)."""

    def __init__(self):
        self.on, self.n = False, 0

    def _seen(self, event, duration, **kw):
        if self.on and event in COMPILE_EVENTS:
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._seen)
        return False


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(root: pathlib.Path, argv, t_start: float, require_tpu: bool = True,
        cache: bool = True, out=None, err=None) -> int:
    """One run; ``require_tpu`` and ``cache`` off let a test drive the rest
    of a run on the CPU without touching JAX's process-wide cache."""
    out = out or sys.stdout
    err = err or sys.stderr
    args = parse(argv)
    try:
        cell = find_cell(root, args.workload)
        if not (root / "src" / "repro").is_dir():
            raise SetupError(f"the program is not at {root / 'src'}")
        if str(root / "src") not in sys.path:
            sys.path.insert(0, str(root / "src"))
        if cache:
            enable_compile_cache(root)
        info = device_info(cell.chips, require_tpu)
    except SetupError as e:
        print(f"perfbench: {e}", file=err)
        return 2
    from perfbench import peaks
    peak = peaks.peak(info["kind"]) if require_tpu else None
    with CompileCounter() as counter:
        result = _measure(cell, args, t_start, info, peak, counter, err)
    print(json.dumps(result), file=out, flush=True)
    return 0


def _measure(cell: Cell, args, t_start: float, info: dict, peak, counter,
             err) -> dict:
    import jax

    from perfbench import checks, xtrace
    readers = {m["name"]: load_reader(cell.metrics_dir, m["name"])
               for m in cell.per_layer} if args.trace else {}
    kind = importlib.import_module(f"perfbench.kinds.{cell.traffic['kind']}")
    session = kind.Session(cell, args.seed, args.seconds)
    setup_s = time.perf_counter() - t_start

    def annotate():
        return (jax.profiler.TraceAnnotation(xtrace.WINDOW) if args.trace
                else contextlib.nullcontext())

    counter.on = True
    if args.trace:
        prof = xtrace.Profile()
        with prof:
            rec = session.window(min(args.seconds, TRACE_S), annotate)
    else:
        rec = session.window(args.seconds, annotate)
    counter.on = False
    info["memory_peak_bytes"] = memory_peak(cell.chips)
    extra = {}
    if args.trace:
        try:
            red = xtrace.reduce(prof.xplane(), cell.chips)
        finally:
            prof.close()
        info["busy_s"] = red.busy_s
        info["window_s"] = red.window_s
        inputs = {**session.layer_inputs(rec), "trace": red, "peak": peak}
        metrics = {}
        for m in cell.per_layer:
            v = readers[m["name"]](inputs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        extra["breakdown"] = {"device_ops": red.device_ops,
                              "idle_gaps": red.idle_gaps}
    else:
        values = {**session.end_to_end(rec), "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    session.release()
    numbers, attempted, failed = session.check(rec)
    ok, table = checks.judge(numbers, cell.limits)
    for name, v in table.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}", file=err)
    return {"correct": ok and failed == 0, "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": info, **extra,
            "window_compiles": counter.n, "checks": table}


def main(t_start: float) -> int:
    try:
        return run(ROOT, sys.argv[1:], t_start)
    except Exception:
        traceback.print_exc()
        return 1
