"""Reduces a ``jax.profiler`` trace of a stretch of the window to numbers.

The stretch is the host event ``chipbench/traced`` that ``Tracer`` writes
around it. On each device plane (``/device:TPU:<n>``) the events of the
``XLA Ops`` line are the operations the chip's core ran, nested where one
runs inside another (a loop and its body); ``Async XLA Ops`` holds the
asynchronous ones in flight beside them. Each event is named by its HLO
instruction's text, which gives the instruction's name, its opcode and
the shapes of its result and operands. From them:

- busy time: the union of the ``XLA Ops`` intervals inside the stretch,
  and the idle share 1 - busy / stretch, averaged over the devices;
- device time per operation, each event counted by its self time (what
  its nested events do not cover), by a name that survives a recompile:
  the instruction's name without its numeric suffix (a Pallas kernel's
  custom call is named after the kernel);
- collective time: the union of the collectives' intervals, an
  asynchronous one from its ``-start`` to the ``-done`` that names it, and
  its exposed part, during which no other operation runs on that device;
- the longest idle gaps, each named by the innermost host event that
  covers the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "chipbench/traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(r"^(all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|collective-broadcast|ragged-all-"
                        r"to-all)(-start|-done)?$")
INSTRUCTION = re.compile(r"^%([^\s=]+) = ")
SUFFIX = re.compile(r"\.\d+$")
TOP = 10


class Tracer:
    """Starts and stops one profiler session around a stretch of steps.

    Python function tracing is off: it would slow the host loop that the
    idle share judges. Host spans come from JAX's own trace points."""

    def __init__(self, directory: str):
        self.dir = directory
        self.done = False
        self._annotation = None

    def start(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(WINDOW)
        self._annotation.__enter__()

    def stop(self):
        import jax
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return found[-1]


def _group(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, "{": 1, ")": -1, "}": -1}.get(text[j], 0)
        if depth == 0:
            return j + 1
    return len(text)


def parse(text: str) -> tuple:
    """(name, opcode, result, operands) of one HLO instruction's text;
    ``("", "", "", "")`` for a text that is not one."""
    m = INSTRUCTION.match(text)
    if not m:
        return "", "", "", ""
    rest = text[m.end():]
    end = _group(rest, 0) if rest.startswith("(") else rest.find(" ")
    if end < 0:
        return m.group(1), "", rest, ""
    result, tail = rest[:end], rest[end:].lstrip()
    op = re.match(r"[\w\-]+", tail)
    if not op or tail[op.end():op.end() + 1] != "(":
        return m.group(1), "", result, ""
    close = _group(tail, op.end())
    return m.group(1), op.group(0), result, tail[op.end() + 1:close - 1]


@dataclass
class Op:
    text: str
    start: int
    end: int

    def __post_init__(self):
        self.name, self.opcode, self.result, self.operands = parse(self.text)

    @property
    def stable(self) -> str:
        return SUFFIX.sub("", self.name) if self.name else self.text[:64]

    @property
    def collective(self):
        return COLLECTIVE.match(self.opcode)


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def collective_spans(ops) -> list:
    """(start, end) of every collective: a synchronous one is its own
    event; an asynchronous one runs from its ``-start`` event to the end
    of the ``-done`` whose operand names that start."""
    spans, started = [], {}
    for op in sorted(ops, key=lambda o: o.start):
        m = op.collective
        if not m:
            continue
        if m.group(2) == "-start":
            started[op.name] = op.start
        elif m.group(2) == "-done":
            ref = re.search(r"%([^\s,)]+)", op.operands)
            begin = started.pop(ref.group(1), op.start) if ref else op.start
            spans.append((begin, op.end))
        else:
            spans.append((op.start, op.end))
    return spans


def self_times(ops) -> list:
    """(op, seconds it ran outside the events nested in it, whether any
    event is nested in it)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    own = [o.end - o.start for o in ops]
    parent = [False] * len(ops)
    stack = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= o.end - o.start
            parent[stack[-1]] = True
        stack.append(i)
    return [(o, max(t, 0) * 1e-9, p) for o, t, p in zip(ops, own, parent)]


@dataclass
class Device:
    ops: list                # the core's operations, ``XLA Ops``
    flights: list = field(default_factory=list)   # ``Async XLA Ops``
    busy: list = field(default_factory=list)

    def __post_init__(self):
        self.busy = union((o.start, o.end) for o in self.ops)


@dataclass
class Summary:
    window: tuple            # (start_ns, end_ns) of the stretch
    devices: list            # Device per chip in the cell
    host: list               # (name, start_ns, end_ns, depth) host events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return (sum(length(d.busy) for d in self.devices)
                / len(self.devices) * 1e-9)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self) -> dict:
        """Device seconds per stable operation name, summed over devices."""
        out = {}
        for d in self.devices:
            for op, secs, _ in self_times(d.ops):
                out[op.stable] = out.get(op.stable, 0.0) + secs
        return out

    def kernel_ops(self, kernel: str) -> list:
        """The calls of a Pallas kernel, whose custom call bears its name."""
        return [op for d in self.devices for op in d.ops
                if op.stable == kernel and op.opcode == "custom-call"]

    def collective_s(self) -> tuple:
        """(collective seconds, exposed seconds), averaged over devices."""
        total = exposed = 0
        for d in self.devices:
            coll = union(clip(collective_spans(d.ops + d.flights),
                              self.window))
            other = union((o.start, o.end) for o, _, nests in self_times(d.ops)
                          if not (o.collective or nests))
            total += length(coll)
            exposed += length(coll) - length(intersect(coll, other))
        n = len(self.devices)
        return total / n * 1e-9, exposed / n * 1e-9

    def idle_gaps(self, count: int = TOP) -> list:
        """The longest idle gaps of the first device, each named by the
        innermost host event covering its middle."""
        busy = self.devices[0].busy
        edges = ([self.window[0]] + [x for iv in busy for x in iv]
                 + [self.window[1]])
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:count]:
            mid = (s + e) // 2
            cover = [h for h in self.host if h[1] <= mid < h[2]
                     and h[0] != WINDOW]
            name = (max(cover, key=lambda h: (h[3], h[1]))[0] if cover
                    else "no host event")
            out.append([name, (e - s) * 1e-9])
        return out

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s / len(self.devices)]
                               for n, s in ops[:TOP]],
                "idle_gaps": self.idle_gaps()}


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(path: str, chips: int) -> Summary | None:
    """The stretch of the trace at ``path``; None where it holds no
    ``chipbench/traced`` event or fewer device planes than ``chips``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, window = [], None
    devices = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: [Op(e.name, int(e.start_ns), int(e.end_ns))
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, ASYNC_LINE)}
            devices[int(m.group(1))] = (lines.get(OPS_LINE, []),
                                        lines.get(ASYNC_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                stack = []
                for e in line.events:
                    s, t = int(e.start_ns), int(e.end_ns)
                    while stack and stack[-1] <= s:
                        stack.pop()
                    host.append((e.name, s, t, len(stack)))
                    stack.append(t)
                    if e.name == WINDOW and window is None:
                        window = (s, t)
    if window is None or len(devices) < chips:
        return None
    inside = lambda ops: [o for o in ops  # noqa: E731
                          if o.end > window[0] and o.start < window[1]]
    chosen = [Device(inside(devices[i][0]), inside(devices[i][1]))
              for i in sorted(devices)[:chips]]
    for d in chosen:
        d.busy = union(clip(d.busy, window))
    return Summary(window, chosen, host)
