"""Programs launched per unit of work: the events of the "XLA Modules"
line whose name matches any ``include`` pattern (all, if none is given) and
no ``exclude`` pattern, counted where they overlap the window (as
`TraceSummary.device_seconds` clips); mean over chips. Nothing to read
where nothing matched: never 0."""

import re


def read(ctx, per, include=(), exclude=()):
    summary, units = ctx["trace"], ctx["units"].get(per)
    inc = [re.compile(p) for p in include]
    exc = [re.compile(p) for p in exclude]
    modules = summary.trace.modules
    count = sum(
        1 for events in modules.values() for e in events
        if min(e.end_ns, summary.hi) > max(e.start_ns, summary.lo)
        and (not inc or any(p.search(e.name) for p in inc))
        and not any(p.search(e.name) for p in exc))
    if not count or not units:
        return None
    return count / len(modules) / units
