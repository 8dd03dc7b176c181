"""Device milliseconds per unit of work of the programs (or operations)
whose names match."""


def read(ctx, per, include=(), exclude=(), line="modules"):
    seconds = ctx["trace"].device_seconds(include, exclude, line)
    if seconds is None or not ctx["units"].get(per):
        return None
    return 1e3 * seconds / ctx["units"][per]
