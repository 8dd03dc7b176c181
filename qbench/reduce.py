"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers use: the device's busy union and idle share, device time by name
pattern, the operations that took most time and the idle gaps by what the
host was doing (the benchmark's own ``qbench.*`` `TraceAnnotation` spans).

The reduction works on `Event` lists, so that tests/qbench can check it on a
small recorded trace and on hand-made events alike; `load_xplane` is the only
part that touches the profiler's file format (through `jax.profiler`)."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "qbench."


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


class Trace(NamedTuple):
    ops: Dict[int, List[Event]]       # chip -> device operations
    modules: Dict[int, List[Event]]   # chip -> executed programs
    spans: List[Event]                # the benchmark's host spans


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                into = ops if line.name == OPS_LINE else modules
                into.setdefault(int(dev.group(1)), []).extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not dev:
                spans.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda e: e.start_ns)
    return Trace(ops, modules, spans)


def union(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of the events, clipped to [lo, hi)."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(ev.start_ns, lo), min(ev.end_ns, hi)) for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def attribute(idle: Sequence[Tuple[float, float]], spans: Sequence[Event]) -> Dict[str, float]:
    """Idle nanoseconds by the host span they fall under; idle time under no
    span goes to ``(outside spans)``. Spans nest on one thread, so the
    innermost (latest started) span that covers an instant takes it."""
    out: Dict[str, float] = {}
    edges = sorted({t for s in spans for t in (s.start_ns, s.end_ns)}
                   | {t for g in idle for t in g})
    j, open_spans = 0, []
    spans = sorted(spans, key=lambda s: s.start_ns)
    gi = 0
    for a, b in zip(edges[:-1], edges[1:]):
        while j < len(spans) and spans[j].start_ns <= a:
            open_spans.append(spans[j])
            j += 1
        open_spans = [s for s in open_spans if s.end_ns > a]
        while gi < len(idle) and idle[gi][1] <= a:
            gi += 1
        if gi < len(idle) and idle[gi][0] <= a and b <= idle[gi][1]:
            name = open_spans[-1].name if open_spans else "(outside spans)"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


class TraceSummary:
    """A traced window, reduced. The window is the extent of the
    benchmark's own spans (the measured loop); device events are clipped to
    it. ``busy_s`` is the mean over the chips that ran anything."""

    def __init__(self, trace: Trace):
        if not trace.spans:
            raise RuntimeError("the trace holds no qbench.* span: nothing marks the window")
        if not trace.ops:
            raise RuntimeError("the trace holds no device operation")
        self.trace = trace
        self.lo = min(s.start_ns for s in trace.spans)
        self.hi = max(s.end_ns for s in trace.spans)
        self.window_s = (self.hi - self.lo) * 1e-9
        self.busy = {chip: union(evs, self.lo, self.hi) for chip, evs in trace.ops.items()}
        per_chip = [sum(e - s for s, e in b) for b in self.busy.values()]
        self.busy_s = sum(per_chip) / len(per_chip) * 1e-9
        self.idle_share = 1.0 - self.busy_s / self.window_s

    def _clipped(self, events: Iterable[Event]) -> Iterable[Tuple[str, float]]:
        for e in events:
            d = min(e.end_ns, self.hi) - max(e.start_ns, self.lo)
            if d > 0:
                yield e.name, d

    def device_seconds(self, include: Sequence[str] = (), exclude: Sequence[str] = (),
                       line: str = "modules", chip: Optional[int] = None) -> Optional[float]:
        """Summed device time of the programs (``line="modules"``) or
        operations (``"ops"``) whose name matches any ``include`` pattern
        (all, if none is given) and no ``exclude`` pattern; mean over chips.
        None when nothing matched: a reader then has nothing to read."""
        inc = [re.compile(p) for p in include]
        exc = [re.compile(p) for p in exclude]
        table = self.trace.modules if line == "modules" else self.trace.ops
        chips = [chip] if chip is not None else sorted(table)
        total, hit = 0.0, False
        for c in chips:
            for name, d in self._clipped(table.get(c, ())):
                if (not inc or any(p.search(name) for p in inc)) and \
                        not any(p.search(name) for p in exc):
                    total += d
                    hit = True
        return total / len(chips) * 1e-9 if hit else None

    def idle_by_span(self, chip: Optional[int] = None) -> Dict[str, float]:
        """Idle seconds of one chip (the first, by default) by host span."""
        chip = min(self.busy) if chip is None else chip
        idle = gaps(self.busy[chip], self.lo, self.hi)
        return {k: v * 1e-9 for k, v in attribute(idle, self.trace.spans).items()}

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.trace.spans if s.name == name)

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        chip = min(self.trace.ops)
        by_op: Dict[str, float] = {}
        for name, d in self._clipped(self.trace.ops[chip]):
            key = op_label(name)
            by_op[key] = by_op.get(key, 0.0) + d * 1e-9
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_span(chip).items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def op_label(name: str) -> str:
    """A short name for an operation of the "XLA Ops" line, whose events
    carry the whole HLO instruction: ``%fusion.1 = f32[1081344,100]{...}
    fusion(...)`` becomes ``fusion.1 f32[1081344,100]``."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def summarize(path: str) -> TraceSummary:
    return TraceSummary(load_xplane(path))


def describe(path: str, top: int = 12) -> str:
    """Planes, lines and the commonest names of a trace: what to look at by
    hand before writing a pattern against it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            counts: Dict[str, List[float]] = {}
            n = 0
            for e in line.events:
                c = counts.setdefault(e.name, [0, 0.0])
                c[0] += 1
                c[1] += e.duration_ns
                n += 1
            out.append(f"  line {line.name!r}: {n} events")
            for name, (cnt, dur) in sorted(counts.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {cnt:7d} x {dur * 1e-6:10.3f} ms  {name[:100]}")
    return "\n".join(out)
