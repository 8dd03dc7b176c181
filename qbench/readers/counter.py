"""A count or a host-clock reading the run made itself (``ctx["counters"]``)."""


def read(ctx, key):
    return ctx.get("counters", {}).get(key)
