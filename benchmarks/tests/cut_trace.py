#!/usr/bin/env python3
"""Cut a recorded `.xplane.pb` down to a slice small enough to keep in git:
the first TPU plane's `XLA Modules` and `XLA Ops` lines and the host plane's
`bench/` spans, inside a window of a few ticks, with every per-event
statistic dropped. A tool for the PR that re-records `data/`; it needs
TensorFlow's xplane protobuf module, which the tests do not.

    python3 benchmarks/tests/cut_trace.py <in.xplane.pb> <out.xplane.pb> [start_ms] [length_ms]
"""

import sys


def cut(src: str, dst: str, start_ms: float = 1000.0, length_ms: float = 130.0) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    device = next(p for p in space.planes if p.name.startswith("/device:TPU:"))
    first_ps = min(ln.timestamp_ns * 1000 + ev.offset_ps
                   for ln in device.lines for ev in ln.events)
    lo, hi = first_ps + int(start_ms * 1e9), first_ps + int((start_ms + length_ms) * 1e9)
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        host = plane.name == "/host:CPU"
        if plane is not device and not host:
            continue
        kept = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if not host and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            events = [ev for ev in line.events
                      if lo <= line.timestamp_ns * 1000 + ev.offset_ps < hi
                      and (not host or plane.event_metadata[ev.metadata_id].name.startswith("bench/"))]
            if not events:
                continue
            new = kept.lines.add(id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
            for ev in events:
                new.events.add(metadata_id=ev.metadata_id, offset_ps=ev.offset_ps,
                               duration_ps=ev.duration_ps)
                meta = plane.event_metadata[ev.metadata_id]
                kept.event_metadata[ev.metadata_id].id = meta.id
                kept.event_metadata[ev.metadata_id].name = meta.name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    cut(sys.argv[1], sys.argv[2], *map(float, sys.argv[3:5]))
