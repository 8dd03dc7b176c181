"""Host milliseconds between a library span's edges and the device program
that ran inside it: ``which="launch"`` is program start minus span start
(argument transfer, launch), ``which="fetch"`` is span end minus program end
(the blocking copy back, waking the thread). Mean over the spans.

The span's stamps come from the library's timeline
(`quiver_tpu.trace.trace_timeline`: ``(name, t0, t1, thread id, ids)`` in
seconds on the process's monotonic clock), the program's from the device
trace (nanoseconds from the profiler session's start). The two clocks differ
by a constant for the session. It is found from an ``anchor`` pair
``[benchmark span, library span]`` of which the first encloses the second one
for one (``qbench.submit`` around ``quiver.serve.submit``): the largest of
(benchmark span's start in the trace) - (library span's start on its own
clock) over the pairs is the offset to within the shortest time the
benchmark's span opened before the library's (a microsecond or two).

Nothing to read (None, never 0) where the library keeps no timeline (a parent
commit), the anchor's two counts differ, more than 1% of the library's spans
stick out of their enclosing span by more than 50 us under that offset, or
fewer than 90% of the spans hold a program."""

import bisect
import re

FIT_NS = 50e3        # a library span may end this far past its enclosing span
FIT_SHARE = 0.99     # ... in at most 1% of the pairs
FOUND_SHARE = 0.90   # spans that must hold a program


def anchor_offset(outer, inner):
    """Nanoseconds to add to a timeline stamp (in ns) to place it in the
    trace, from ``outer`` (the benchmark's `Event`s) and ``inner`` ((t0_ns,
    t1_ns) of the library spans they enclose), both in start order; None
    where the counts differ or the spans do not fit."""
    if not outer or len(outer) != len(inner):
        return None
    offset = max(o.start_ns - t0 for o, (t0, _) in zip(outer, inner))
    fit = sum(1 for o, (_, t1) in zip(outer, inner) if t1 + offset <= o.end_ns + FIT_NS)
    return offset if fit >= FIT_SHARE * len(outer) else None


def program_gaps(spans, programs):
    """For each (start_ns, end_ns) of ``spans``, in start order, the first
    program `Event` not yet taken that lies inside it: ``(launch_ns,
    fetch_ns)``, or None for a span that holds none."""
    programs = sorted(programs, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in programs]
    taken = [False] * len(programs)
    out = []
    for s0, s1 in sorted(spans):
        found = None
        for i in range(bisect.bisect_left(starts, s0), len(programs)):
            if starts[i] > s1:
                break
            if not taken[i] and programs[i].end_ns <= s1:
                taken[i] = True
                found = (starts[i] - s0, s1 - programs[i].end_ns)
                break
        out.append(found)
    return out


def read(ctx, anchor, span, module, which):
    from quiver_tpu import trace as qtrace

    side = ("launch", "fetch").index(which)
    timeline = getattr(qtrace, "trace_timeline", None)
    if timeline is None:
        return None
    by_name = {anchor[1]: [], span: []}
    for name, t0, t1, *_ in timeline():
        if name in by_name:
            by_name[name].append((t0 * 1e9, t1 * 1e9))
    trace = ctx["trace"].trace
    outer = [e for e in trace.spans if e.name == anchor[0]]  # in start order
    offset = anchor_offset(outer, sorted(by_name[anchor[1]]))
    if offset is None or not by_name[span]:
        return None
    pattern = re.compile(module)
    programs = [e for e in trace.modules[min(trace.modules)] if pattern.search(e.name)]
    gaps = program_gaps([(t0 + offset, t1 + offset) for t0, t1 in by_name[span]], programs)
    found = [g for g in gaps if g is not None]
    if len(found) < FOUND_SHARE * len(gaps):
        return None
    return 1e-6 * sum(g[side] for g in found) / len(found)
