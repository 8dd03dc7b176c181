"""Host milliseconds the library recorded itself under a span name
(`quiver_tpu.trace.trace_report`: `trace_scope` spans and `observe`d
durations): the total over a unit of work, or the mean of a recorded event
when ``per`` is absent. The library records only while a profiler session
is open and one run is one process, so the registry holds the traced window
and nothing else. Nothing to read where the program has no such span (a
parent commit, a renamed span): never 0."""


def read(ctx, name, per=None):
    from quiver_tpu.trace import trace_report

    entry = trace_report().get(name)
    if not entry or not entry[0]:
        return None
    count, total_s = entry[0], entry[1]
    if per is None:
        return 1e3 * total_s / count
    units = ctx["units"].get(per)
    return 1e3 * total_s / units if units else None
