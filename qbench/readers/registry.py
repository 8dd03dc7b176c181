"""The mean of what the library recorded under a name in its trace registry
(`quiver_tpu.trace.trace_report`), as it is: a counter's value per event (bytes
a step), where `scope` reads host seconds as milliseconds. The library
records only while a profiler session is open and one run is one process, so
the registry holds the traced window alone. Nothing to read where the
program has no such counter (a parent commit, a renamed counter): never 0."""


def read(ctx, name):
    from quiver_tpu.trace import trace_report

    entry = trace_report().get(name)
    if not entry or not entry[0]:
        return None
    return entry[1] / entry[0]
