"""Nestable span tracing on the profiler's clock, with Chrome-trace/
Perfetto export.

Every span is also a `jax.profiler.TraceAnnotation`: while a profiler
session records (`jax.profiler.trace`, or a benchmark's traced window) it
lands on the session's host plane, on the same clock as the device's ops,
with its keyword arguments as the event's stats. The arguments are encoded
only while a session records.

A `Tracer` additionally records wall-clock spans (monotonic
`perf_counter_ns`, thread-safe, nesting tracked per thread) and exports
them as the Chrome trace-event JSON that Perfetto / `chrome://tracing`
load directly. The module-level tracer is DISABLED by default: with no
profiler session `span()` then returns a shared null context manager — no
allocation, no clock read — so instrumented hot paths cost nothing until
someone calls `configure_tracing(True)` (the `--trace-out` CLI flag does)
or starts a profiler session.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

# True while a profiler session records; TraceMe's own static check.
_profiling = TraceAnnotation.is_enabled


class _NullContext:
    """Shared do-nothing context manager for the disabled-tracer path."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullContext()


def _annotation(name: str, args: dict):
    """The profiler's host span, or the null context with no session."""
    return TraceAnnotation(name, **args) if _profiling() else _NULL


class _Span:
    __slots__ = ("name", "t0_ns", "args", "depth", "parent")

    def __init__(self, name, t0_ns, args, depth, parent):
        self.name = name
        self.t0_ns = t0_ns
        self.args = args
        self.depth = depth
        self.parent = parent


class Tracer:
    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self._events: list[dict] = []   # completed chrome "X" events
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._t0_ns = time.perf_counter_ns()   # trace-relative origin

    # -- recording ---------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time a block. Nesting is tracked per thread: the exported
        event carries its depth and parent span name in ``args``. The
        block is a profiler annotation too, enabled or not."""
        with _annotation(name, args):
            if not self.enabled:
                yield None
                return
            stack = self._stack()
            parent = stack[-1].name if stack else None
            sp = _Span(name, time.perf_counter_ns(), args, len(stack), parent)
            stack.append(sp)
            try:
                yield sp
            finally:
                stack.pop()
                t1 = time.perf_counter_ns()
                ev_args = {"depth": sp.depth}
                if sp.parent is not None:
                    ev_args["parent"] = sp.parent
                ev_args.update(sp.args)
                ev = {
                    "name": name,
                    "ph": "X",
                    "ts": (sp.t0_ns - self._t0_ns) / 1e3,    # µs
                    "dur": (t1 - sp.t0_ns) / 1e3,            # µs
                    "pid": os.getpid(),
                    "tid": threading.get_ident(),
                    "args": ev_args,
                }
                with self._lock:
                    self._events.append(ev)

    def traced(self, name: str | None = None):
        """Decorator form of `span` (span name defaults to the function's
        qualified name)."""
        def deco(fn):
            label = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with self.span(label):
                    return fn(*a, **kw)
            return wrapper
        return deco

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event (chrome ``ph: "i"``)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "p",
              "ts": (time.perf_counter_ns() - self._t0_ns) / 1e3,
              "pid": os.getpid(), "tid": threading.get_ident(),
              "args": dict(args)}
        with self._lock:
            self._events.append(ev)

    # -- export ------------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def chrome_trace(self) -> dict:
        """The Chrome trace-event document Perfetto loads as-is."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path) -> dict:
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return doc

    def span_stats(self) -> dict[str, dict]:
        """Per-span-name aggregates over the recorded complete events:
        ``{name: {count, total_s, mean_s, max_s}}`` — what the roofline
        measured-timing path consumes."""
        agg: dict[str, list[float]] = {}
        for ev in self.events():
            if ev.get("ph") == "X":
                agg.setdefault(ev["name"], []).append(ev["dur"] / 1e6)
        return {
            name: {"count": len(d), "total_s": sum(d),
                   "mean_s": sum(d) / len(d), "max_s": max(d)}
            for name, d in sorted(agg.items())
        }


_GLOBAL = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    global _GLOBAL
    _GLOBAL = tracer
    return tracer


def configure_tracing(enabled: bool = True) -> Tracer:
    """Flip the global tracer; returns it (fresh event buffer NOT
    implied — call `clear()` for that)."""
    _GLOBAL.enabled = enabled
    return _GLOBAL


def span(name: str, **args):
    """Span on the global tracer and the profiler: a bare
    `TraceAnnotation` while only a profiler session records, and a shared
    null context (no allocation, no clock read) while neither does, so
    call sites in hot loops stay free."""
    if _GLOBAL.enabled:
        return _GLOBAL.span(name, **args)
    if _profiling():
        return TraceAnnotation(name, **args)
    return _NULL
