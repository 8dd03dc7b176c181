"""A kernel's share of its roofline: the least time the chip could take for
the work (`qbench.work`: bytes over peak bytes/s, operations over peak
FLOP/s, whichever is larger) over the device time of the programs whose
names match. Nothing to read where the kernel did not run: never 0."""


def read(ctx, per, include=(), exclude=(), line="modules", bytes_key=None, flops_key=None):
    seconds = ctx["trace"].device_seconds(include, exclude, line)
    peaks, units = ctx["peaks"], ctx["units"].get(per)
    if seconds is None or not peaks or not units:
        return None
    least = max(ctx["work"][bytes_key] / peaks["hbm_bytes_per_s"] if bytes_key else 0.0,
                ctx["work"][flops_key] / peaks["flops_per_s"] if flops_key else 0.0)
    return 100.0 * least * units / seconds
