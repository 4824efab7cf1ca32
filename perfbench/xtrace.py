"""Profiler trace of a measured window, reduced to device busy time, the
device operations that took most time, and the idle gaps by what the
host's benchmark thread was doing.

The window is the host span `WINDOW` that the harness opens around it.
Busy time is the union of the intervals of the operations on each used
device's op line, clipped to the window, averaged over the devices. No
program name is looked up: the reduction reads whatever the trace holds.
"""
from __future__ import annotations

import dataclasses
import heapq
import pathlib
import shutil
import tempfile

WINDOW = "perfbench.window"
DEVICE_PREFIX = "/device:"
OP_LINES = ("XLA Ops",)            # per-op line of a device plane
TOP = 10


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def covered(merged) -> float:
    return float(sum(e - s for s, e in merged))


def gaps(merged, lo, hi):
    """Idle intervals of [lo, hi] between the merged busy intervals."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over the used devices
    device_ops: list              # [[name, seconds]] summed over devices
    idle_gaps: list               # [[host activity, idle seconds]]
    n_device_events: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _device_planes(planes, n_devices: int):
    """The chips' planes, `/device:<kind>:<n>` with an op line, by ``n``. A
    TPU trace also holds planes such as `/device:CUSTOM:Megascale Trace`,
    which run no operation."""
    dev = []
    for p in planes:
        kind, _, idx = p.name[len(DEVICE_PREFIX):].rpartition(":")
        if (p.name.startswith(DEVICE_PREFIX) and idx.isdigit()
                and kind != "CPU"
                and any(ln.name in OP_LINES for ln in p.lines)):
            dev.append((int(idx), p))
    dev.sort(key=lambda x: x[0])
    return [p for _, p in dev[:n_devices]]


def op_name(text: str) -> str:
    """A TPU trace names an op by its whole HLO instruction text; keep the
    instruction's name (`%while.21`), which carries its opcode."""
    return text.split(" = ", 1)[0]


def _op_events(plane):
    for ln in plane.lines:
        if ln.name in OP_LINES:
            for ev in ln.events:
                yield (op_name(ev.name), ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)


def _label_gaps(host_events, gap_list):
    """What the host thread was doing in each gap: the host spans that
    cover the gap's midpoint, outermost first (at most three)."""
    evs = sorted(host_events, key=lambda x: x[1])
    order = sorted(range(len(gap_list)),
                   key=lambda i: gap_list[i][0] + gap_list[i][1])
    labels = [None] * len(gap_list)
    active, k = [], 0
    for gi in order:
        mid = 0.5 * (gap_list[gi][0] + gap_list[gi][1])
        while k < len(evs) and evs[k][1] <= mid:
            heapq.heappush(active, (evs[k][2], k))
            k += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        cover = sorted((evs[j] for _, j in active),
                       key=lambda x: x[1] - x[2])
        names = [c[0] for c in cover][-3:]
        labels[gi] = " > ".join(names) if names else "no host span"
    return labels


def reduce(path, n_devices: int) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)
    window, thread = None, None
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                    thread = ln
                    break
            if window:
                break
        if window:
            break
    if window is None:
        raise RuntimeError(f"the trace has no {WINDOW!r} span")
    lo, hi = window
    devs = _device_planes(planes, n_devices)
    if len(devs) < n_devices:
        raise RuntimeError(
            f"the trace has {len(devs)} device planes, {n_devices} used")
    busy, op_time, n_ev, first_merged = [], {}, 0, None
    for d in devs:
        ivs = []
        for name, s, e in _op_events(d):
            if e <= lo or s >= hi:
                continue
            ivs.append((s, e))
            op_time[name] = op_time.get(name, 0.0) + min(e, hi) - max(s, lo)
        n_ev += len(ivs)
        m = clip(merge(ivs), lo, hi)
        busy.append(covered(m))
        if first_merged is None:
            first_merged = m
    host = [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in thread.events if ev.name != WINDOW]
    g = gaps(first_merged, lo, hi)
    idle = {}
    for (s, e), lab in zip(g, _label_gaps(host, g)):
        idle[lab] = idle.get(lab, 0.0) + (e - s)
    ops = sorted(op_time.items(), key=lambda x: -x[1])[:TOP]
    idle_top = sorted(idle.items(), key=lambda x: -x[1])[:TOP]
    return Reduction(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                     device_ops=[[n, t] for n, t in ops],
                     idle_gaps=[[n, t] for n, t in idle_top],
                     n_device_events=n_ev)


class Profile:
    """Profiler session writing to a fresh directory under TMPDIR, which
    `close` removes after the trace has been reduced."""

    def __init__(self):
        self.dir = pathlib.Path(tempfile.mkdtemp(prefix="perfbench-trace-"))

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        # The Python tracer records every Python call: it slows the host
        # path many times over and is not what the reduction reads.
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def xplane(self) -> pathlib.Path:
        found = sorted(self.dir.rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        return found[-1]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
