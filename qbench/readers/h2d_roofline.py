"""The host link's share of its roofline in a tiered step: the least time the
link could take to bring the chip a step's valid cold bytes (`ctx["work"]`,
`qbench.work_tiered`; over ``link_bytes_per_s``, which the metric's own file
states with its ``link_source``) over the time the library's copy span took
(``quiver.feature.h2d``: from a batch's first `device_put` until its arrays
are ready on the chip). The span is counted once a batch, so its count is the
number of steps it covers. On a device without published peaks (the tests'
CPU rehearsal), or where the program has no such span (a parent commit),
there is nothing to read: never 0."""


def read(ctx, name, bytes_key, link_bytes_per_s, link_source=None):
    from quiver_tpu.trace import trace_report

    entry = trace_report().get(name)
    if not entry or not entry[0] or not entry[1] or not ctx["peaks"]:
        return None
    batches, seconds = entry[0], entry[1]
    return 100.0 * ctx["work"][bytes_key] * batches / link_bytes_per_s / seconds
