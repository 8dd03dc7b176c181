"""The whole step's share of the chip's peak: the operations the algorithm
needs per unit of work (`qbench.work`), times the units done in the traced
window, over the window and the peak FLOP/s. Idle time counts against it."""


def read(ctx, per, flops_key):
    peaks, units = ctx["peaks"], ctx["units"].get(per)
    if not peaks or not units:
        return None
    return 100.0 * ctx["work"][flops_key] * units / ctx["trace"].window_s / peaks["flops_per_s"]
