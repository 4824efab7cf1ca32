"""The program's own spans and scopes in a profiler trace's measured
window, which `xtrace.reduce` does not read:

- host spans: for each span name on the window's host thread (the line of
  the `xtrace.WINDOW` span), the durations of its events that lie inside
  the window;
- device scopes: for each component of an op's `op_name` path (what
  `jax.named_scope` writes) on the used device planes, the union of the
  intervals of the ops that carry it, clipped to the window, in seconds,
  averaged over the devices. Ops that carry no path count under
  `NO_PATH`.

On a TPU the path is the `tf_op` stat of an "XLA Ops" event's metadata
(`jit(step)/fixture.scope/jit(sort)/sort:`, the op type after the last
colon, empty for JAX). `jax.profiler.ProfileData` does not expose metadata
stats, so the device planes are read from the file's protocol buffer
(`decode_planes`). Ops that the compiler makes without metadata (layout
copies, loops that stand for a scatter) carry no path. As in `xtrace`, no
program name is looked up.
"""
from __future__ import annotations

import dataclasses
import pathlib

from perfbench import xtrace

PATH_STAT = "tf_op"
NO_PATH = "no op_name"


@dataclasses.dataclass
class Scopes:
    window_s: float
    host_spans: dict            # {span name: [seconds, ...]}
    device_scopes: dict         # {path component: seconds}


# -- the XSpace protocol buffer, as much of it as the scopes need ----------
def _varint(buf, i):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, the bytes for a length-delimited field, skipping fixed ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class Line:
    name: str
    events: list                # [(metadata id, start ns, end ns)]


@dataclasses.dataclass
class Plane:
    name: str
    lines: list
    paths: dict                 # {event metadata id: op_name path}


def _map_values(entries):
    """The values of a protocol buffer map field's entries."""
    for raw in entries:
        for f, v in _fields(raw):
            if f == 2:
                yield v


def _stat_names(entries) -> dict:
    """{stat metadata id: stat name} of a plane's XStatMetadata map."""
    out = {}
    for md in _map_values(entries):
        f = dict(_fields(md))
        out[f.get(1, 0)] = bytes(f.get(2, b"")).decode()
    return out


def _paths(entries, stat_names: dict) -> dict:
    """{event metadata id: `op_name` path} of the events whose metadata
    carries `PATH_STAT`, as a string or a reference to an interned one."""
    out = {}
    for md in _map_values(entries):
        mid = 0
        for f, v in _fields(md):
            if f == 1:
                mid = v
            elif f == 5:
                st = dict(_fields(v))
                if stat_names.get(st.get(1)) != PATH_STAT:
                    continue
                text = (bytes(st[5]).decode() if 5 in st
                        else stat_names.get(st.get(7)))
                if text:
                    out[mid] = text
    return out


def _line(raw) -> Line:
    """A line's name, and on an op line its events in ns."""
    name, ts, raw_events = "", 0, []
    for f, v in _fields(raw):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            ts = _signed(v)
        elif f == 4:
            raw_events.append(v)
    events = []
    if name in xtrace.OP_LINES:
        for ev in raw_events:
            f = dict(_fields(ev))
            s = ts + f.get(2, 0) * 1e-3
            events.append((f.get(1, 0), s, s + f.get(3, 0) * 1e-3))
    return Line(name, events)


def _plane(buf) -> Plane:
    fields = {3: [], 4: [], 5: []}
    name = ""
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f in fields:
            fields[f].append(v)
    if not name.startswith(xtrace.DEVICE_PREFIX):
        return Plane(name, [], {})
    return Plane(name, [_line(raw) for raw in fields[3]],
                 _paths(fields[4], _stat_names(fields[5])))


def decode_planes(data: bytes) -> list:
    """The planes of a serialized XSpace: the name of every plane, and the
    lines, op events and `op_name` paths of the device planes."""
    return [_plane(v) for f, v in _fields(memoryview(data)) if f == 1]


# -- reductions --------------------------------------------------------------
def components(path: str) -> set:
    """Scope components of a `tf_op` path, its op type dropped."""
    name = path.rpartition(":")[0] if ":" in path else path
    return {c for c in name.split("/") if c}


def scope_time(ops, lo: float, hi: float) -> dict:
    """``ops`` (start, end, path or None) of one device: for each path
    component, the union of its ops' intervals clipped to [lo, hi]."""
    ivs = {}
    for s, e, path in ops:
        if e <= lo or s >= hi:
            continue
        for c in components(path) if path else (NO_PATH,):
            ivs.setdefault(c, []).append((s, e))
    return {c: xtrace.covered(xtrace.clip(xtrace.merge(v), lo, hi))
            for c, v in ivs.items()}


def span_times(events, lo: float, hi: float) -> dict:
    """``events`` (name, start, end) of one host thread: for each name,
    the durations of its events inside [lo, hi], in order of start."""
    out = {}
    for name, s, e in sorted(events, key=lambda x: x[1]):
        if s >= lo and e <= hi and name != xtrace.WINDOW:
            out.setdefault(name, []).append(e - s)
    return out


def _window(pd):
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name == xtrace.WINDOW:
                    return (ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9, ln)
    raise RuntimeError(f"the trace has no {xtrace.WINDOW!r} span")


def read(path, n_devices: int) -> Scopes:
    """The host spans and device scopes of the trace file's window, on the
    first ``n_devices`` device planes (as `xtrace.reduce` takes them)."""
    from jax.profiler import ProfileData
    data = pathlib.Path(path).read_bytes()
    lo, hi, thread = _window(ProfileData.from_serialized_xspace(data))
    host = [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in thread.events]
    devs = xtrace._device_planes(decode_planes(data), n_devices)
    if len(devs) < n_devices:
        raise RuntimeError(
            f"the trace has {len(devs)} device planes, {n_devices} used")
    per_dev = []
    for d in devs:
        ops = [(s * 1e-9, e * 1e-9, d.paths.get(mid))
               for ln in d.lines if ln.name in xtrace.OP_LINES
               for mid, s, e in ln.events]
        per_dev.append(scope_time(ops, lo, hi))
    names = set().union(*per_dev)
    return Scopes(window_s=hi - lo, host_spans=span_times(host, lo, hi),
                  device_scopes={c: sum(d.get(c, 0.0) for d in per_dev)
                                 / len(per_dev) for c in sorted(names)})
