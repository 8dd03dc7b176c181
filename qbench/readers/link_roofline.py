"""An exchange's share of its roofline: the least time the inter-chip links
could take to bring a chip the bytes it does not hold (`ctx["work"]`, kept
with the benchmark; over ``link_bytes_per_s``, the chip's published link
bandwidth, which the metric's own file states with its ``link_source``) over
the device time of the collective operations whose names match. On a device
without published peaks (the tests' CPU rehearsal), or where no collective
ran, there is nothing to read: never 0."""


def read(ctx, per, bytes_key, link_bytes_per_s, link_source=None,
         include=(), exclude=(), line="ops"):
    seconds = ctx["trace"].device_seconds(include, exclude, line)
    units = ctx["units"].get(per)
    if seconds is None or not ctx["peaks"] or not units:
        return None
    return 100.0 * ctx["work"][bytes_key] / link_bytes_per_s * units / seconds
