"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
