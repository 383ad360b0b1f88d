"""Device busy time in the window per online decision, in milliseconds."""


def read(ctx):
    trace, stats = ctx["trace"], ctx["stats"]
    if trace is None or not stats.get("decisions") or not trace.n_ops:
        return None
    return 1e3 * trace.busy_s() / stats["decisions"]
