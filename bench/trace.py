"""Reduce a profiler trace (``.xplane.pb``) to device-time numbers.

The trace is read with ``jax.profiler.ProfileData``; nothing of the program
is imported.  A device is a plane whose name starts with ``/device:TPU:``
(not ``TPU_SYSTEM``); its operations are the events of its ``XLA Ops``
line, named by their HLO instruction (a Pallas kernel by the name of the
function that calls ``pallas_call``, such as ``terapipe_attention_fwd.23``).
Operations that contain others on that line (a ``while`` around its
body, a ``conditional`` around its branch) are containers: only the leaf
operations count as work, so nothing is counted twice.  Host spans are the
events of the host planes, among them the benchmark's own
``TraceAnnotation``s (``bench_window``, ``data``, ``dispatch``, ``sync``).

All times are nanoseconds on the trace's clock unless a name says ``_s``.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SKEW_MAX = 50e6          # ns: a device op this long before the window is not its
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"send|recv", re.I)


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]          # plane name -> leaf ops, by start
    host: List[Op]                        # every host event


def leaf_ops(ops: Iterable[Op]) -> List[Op]:
    """The ops that contain no other op of the same line."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    leaf = [True] * len(ops)
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            leaf[stack[-1]] = False
        stack.append(i)
    return [o for o, keep in zip(ops, leaf) if keep]


_INSTR = re.compile(r"%?([^\s=%]+)\s*=")


def op_name(text: str) -> str:
    """The HLO instruction name of an ``XLA Ops`` event: its text is the
    whole instruction, ``%name = type op(operands...)``, whose operands name
    other instructions."""
    m = _INSTR.match(text)
    return m.group(1) if m else text


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [Op(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[plane.name] = leaf_ops(ops)
        elif plane.name.startswith("/host:"):
            host.extend(Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events)
    return Trace(devices, sorted(host, key=lambda o: o.start))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the disjoint sorted intervals ``a`` outside ``b``."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def window(trace: Trace, name: str = "bench_window") -> Interval:
    """The span of the host annotation ``name`` (the traced window)."""
    spans = [o for o in trace.host if o.name == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return spans[0].start, spans[0].end


def device_windows(trace: Trace, name: str = "bench_window",
                   first: str = "dispatch") -> Dict[str, Interval]:
    """The host window on each device's clock.  A device's clock may run
    behind the host's (by about 0.7 ms on a v5e): where its first op inside
    the window starts before the first ``first`` host span, the window is
    moved back by that much, so no op precedes the dispatch that caused it."""
    lo, hi = window(trace, name)
    starts = [o.start for o in trace.host if o.name == first and o.start >= lo]
    t0 = min(starts) if starts else lo
    out = {}
    for dev, ops in trace.devices.items():
        firsts = [o.start for o in ops if o.start > lo - SKEW_MAX]
        skew = max(0.0, t0 - min(firsts)) if firsts else 0.0
        out[dev] = (lo - skew, hi - skew)
    return out


def busy(ops: Iterable[Op], win: Interval) -> List[Interval]:
    """The union of the ops' intervals inside the window."""
    return union(clip(((o.start, o.end) for o in ops), *win))


def op_time(ops: Iterable[Op], pattern: str, win: Interval) -> Tuple[float, int]:
    """(summed duration, count) of the ops whose name matches ``pattern``
    and that start inside the window."""
    rx = re.compile(pattern)
    hits = [o for o in ops if rx.search(o.name) and win[0] <= o.start < win[1]]
    return sum(o.dur for o in hits), len(hits)


def exposed_collective(ops: Sequence[Op], win: Interval,
                       rx: re.Pattern = COLLECTIVE) -> float:
    """Time inside the window in which a collective runs and no other op
    does (the collective's time not hidden behind compute)."""
    coll = busy((o for o in ops if rx.search(o.name)), win)
    compute = busy((o for o in ops if not rx.search(o.name)), win)
    return length(subtract(coll, compute))


def top_ops(trace: Trace, wins: Dict[str, Interval], n: int = 10) -> List[list]:
    """The ``n`` op names with the most device time in the window, summed
    over devices and averaged per device: ``[[name, seconds], ...]``."""
    tot: Dict[str, float] = {}
    for dev, ops in trace.devices.items():
        lo, hi = wins[dev]
        for o in ops:
            if lo <= o.start < hi:
                tot[o.name] = tot.get(o.name, 0.0) + o.dur
    nd = max(1, len(trace.devices))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / nd / 1e9] for name, t in best]


HOST_SPANS = ("data", "dispatch", "sync")


def idle_gaps(trace: Trace, wins: Dict[str, Interval], n: int = 10,
              spans: Sequence[str] = HOST_SPANS) -> List[list]:
    """The ``n`` longest idle gaps of the first device in the window, each
    named by the host span that overlaps it most and the device op that
    ended just before it:
    ``[["<span> after <op> @<offset ms>", seconds], ...]``."""
    if not trace.devices:
        return []
    dev = sorted(trace.devices)[0]
    ops, win = trace.devices[dev], wins[dev]
    shift = window(trace)[0] - win[0]          # device clock -> host clock
    gaps = subtract([win], busy(ops, win))
    host = [o for o in trace.host if o.name in spans]
    by_end = sorted((o.end, o.name) for o in ops)
    ends = [e for e, _ in by_end]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "none", 0.0
        for h in host:
            c = min(b + shift, h.end) - max(a + shift, h.start)
            if c > cover:
                best, cover = h.name, c
        i = bisect.bisect_right(ends, a) - 1
        prev = by_end[i][1] if i >= 0 else "start"
        out.append([f"{best} after {prev} @{(a - win[0]) / 1e6:.3f}ms",
                    (b - a) / 1e9])
    return out


def device_busy_s(trace: Trace, wins: Dict[str, Interval]) -> Dict[str, float]:
    return {d: length(busy(ops, wins[d])) / 1e9
            for d, ops in trace.devices.items()}
