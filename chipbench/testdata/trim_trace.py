"""Cuts a recorded trace down to a few steps, for the reduction's test.

    python3 chipbench/testdata/trim_trace.py <in.xplane.pb> <out.xplane.pb> [steps]

Keeps, on every line, the events that overlap the first ``steps`` training
steps (the long events of the ``Steps`` line of ``/device:TPU:0``) inside
the ``chipbench/traced`` stretch, with a millisecond on either side, and
makes that interval the new stretch. Needs the XPlane protocol buffers
(``tensorflow.tsl.profiler.protobuf``).
"""
from __future__ import annotations

import sys

WINDOW = "chipbench/traced"
MARGIN_PS = 1_000_000_000


def _abs(line, event) -> int:
    return line.timestamp_ns * 1000 + event.offset_ps


def trim(src: str, dst: str, steps: int = 2) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    def window_events():
        for plane in space.planes:
            for line in plane.lines:
                for e in line.events:
                    if plane.event_metadata[e.metadata_id].name == WINDOW:
                        yield line, e

    line, event = next(window_events())
    w_lo, w_hi = _abs(line, event), _abs(line, event) + event.duration_ps
    device = next(p for p in space.planes if p.name == "/device:TPU:0")
    step_line = next(l for l in device.lines if l.name == "Steps")
    inside = sorted((_abs(step_line, e), _abs(step_line, e) + e.duration_ps)
                    for e in step_line.events
                    if w_lo <= _abs(step_line, e)
                    and _abs(step_line, e) + e.duration_ps <= w_hi)
    longest = max(hi - lo for lo, hi in inside)
    chosen = [iv for iv in inside if iv[1] - iv[0] > longest / 2][:steps]
    lo, hi = chosen[0][0] - MARGIN_PS, chosen[-1][1] + MARGIN_PS
    for plane in space.planes:
        used = set()
        for line in plane.lines:
            kept = [e for e in line.events
                    if _abs(line, e) < hi and _abs(line, e) + e.duration_ps > lo]
            del line.events[:]
            line.events.extend(kept)
            used.update(e.metadata_id for e in kept)
        for mid in [m for m in plane.event_metadata if m not in used]:
            del plane.event_metadata[mid]
    for line, event in window_events():
        event.offset_ps = lo - line.timestamp_ns * 1000
        event.duration_ps = hi - lo
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:]))
