"""Device-idle milliseconds per unit of work that fall under the named host
spans of the benchmark's loop (``qbench.sample_dense``, ...)."""


def read(ctx, spans, per):
    idle = ctx["trace"].idle_by_span()
    found = [idle[s] for s in spans if s in idle]
    if not found or not ctx["units"].get(per):
        return None
    return 1e3 * sum(found) / ctx["units"][per]
