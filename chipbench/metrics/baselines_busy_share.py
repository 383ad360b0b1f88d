"""Device time of the four heuristics' programs (DRF, fairness,
bin-packing, spreading; programs.py) as a share of the device's busy
time."""
import programs


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.busy_s() <= 0:
        return None
    t = programs.baselines_seconds(trace)
    return 100.0 * t / trace.busy_s() if t > 0 else None
