"""Device time of the four heuristics' lifecycle programs (DRF, fairness,
bin-packing, spreading) as a share of the device's busy time: the
``jit__grid_lifecycle`` executions after OGASched's in each call, in the
order of ``drive.ALGORITHMS`` (``run_grid``'s dispatch order)."""
import drive

PROGRAM = r"^jit__grid_lifecycle$"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.busy_s() <= 0:
        return None
    by = trace.by_order(PROGRAM, drive.ALGORITHMS)
    t = sum(v for k, v in by.items() if k != "ogasched") if by else 0.0
    return 100.0 * t / trace.busy_s() if t > 0 else None
