"""Share of the traced window in which no operation ran on the chips, in a
sweep or replay cell: 100 * (1 - busy / window), busy being the union of
the device op intervals in the trace, averaged over the chips."""


def read(ctx):
    return ctx["trace"].idle_pct() if ctx["trace"] is not None else None
