"""Device time of the OGASched programs (programs.py) as a share of the
device's busy time."""
import programs


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.busy_s() <= 0:
        return None
    t = programs.oga_seconds(trace)
    return 100.0 * t / trace.busy_s() if t > 0 else None
