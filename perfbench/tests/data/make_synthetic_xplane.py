"""Writes `synthetic.xplane.pb`: a profiler trace laid out as JAX writes
one on a TPU, with times chosen by hand, for the trace reduction's test.

    python3 perfbench/tests/data/make_synthetic_xplane.py

Needs TensorFlow's copy of the XPlane protocol buffer; the test reads the
written file with `jax.profiler.ProfileData` alone.

Host thread (times in ms): the window span 1-11, `host.wait` 2-4 and
6.5-8. Device "XLA Ops": 0.5-1.5, 1.2-2, 4-5, 4.5-6.5, 8-9, 10.5-12
(busy inside the window: 1-2, 4-6.5, 8-9, 10.5-11 = 5 ms; idle 5 ms, of
which 2-4 and 6.5-8 under `host.wait`). The "XLA Modules" line spans
1-11 and is not an op line. As on a v5e, the trace also holds planes that
run no operation: `/device:CUSTOM:Megascale Trace` (which sorts before
`/device:TPU:0`), `#Chip0 Host Interface` and an empty `/host:metadata`.
"""
import pathlib

from tensorflow.tsl.profiler.protobuf import xplane_pb2

MS = 10**9     # picoseconds


def plane(space, pid, name, lines):
    p = space.planes.add(id=pid, name=name)
    names = {}
    for lid, (lname, events) in enumerate(lines):
        ln = p.lines.add(id=lid, display_id=lid, name=lname, timestamp_ns=0)
        for ename, s, e in events:
            if ename not in names:
                names[ename] = len(names) + 1
                md = p.event_metadata[names[ename]]
                md.id, md.name = names[ename], ename
            ln.events.add(metadata_id=names[ename], offset_ps=int(s * MS),
                          duration_ps=int((e - s) * MS))


def main():
    space = xplane_pb2.XSpace()
    plane(space, 3, "#Chip0 Host Interface", [])
    plane(space, 4, "/host:metadata", [])
    plane(space, 5, "/device:CUSTOM:Megascale Trace",
          [("Megascale", [("transfer", 0, 12)])])
    plane(space, 1, "/host:CPU", [
        ("python", [("perfbench.window", 1, 11), ("host.wait", 2, 4),
                    ("host.wait", 6.5, 8)]),
        ("other", [("unrelated", 0, 12)])])
    plane(space, 2, "/device:TPU:0", [
        ("XLA Modules", [("jit_step", 1, 11)]),
        ("XLA Ops", [("fusion.1", 0.5, 1.5), ("fusion.2", 1.2, 2),
                     ("scatter.3", 4, 5), ("fusion.1", 4.5, 6.5),
                     ("scatter.3", 8, 9), ("fusion.2", 10.5, 12)])])
    out = pathlib.Path(__file__).with_name("synthetic.xplane.pb")
    out.write_bytes(space.SerializeToString())


if __name__ == "__main__":
    main()
