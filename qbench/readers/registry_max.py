"""The longest single reading the library recorded under a name in its trace
registry (`quiver_tpu.trace.trace_report(with_max=True)`), in milliseconds:
beside `scope`'s mean, the one outlier a mean hides (the longest overshoot of
the stall watch's 5 ms tick is the longest time every Python thread of the
process stood still). Nothing to read where the program has no such name (a
parent commit): never 0."""


def read(ctx, name):
    from quiver_tpu.trace import trace_report

    entry = trace_report(with_max=True).get(name)
    if not entry or not entry[0]:
        return None
    return 1e3 * entry[2]
