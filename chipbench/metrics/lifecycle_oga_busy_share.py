"""Device time of OGASched's lifecycle program as a share of the device's
busy time. A lifecycle sweep call runs one ``jit__grid_lifecycle``
execution per algorithm, in the order of ``drive.ALGORITHMS``
(``run_grid``'s dispatch order); OGASched's is the first."""
import drive

PROGRAM = r"^jit__grid_lifecycle$"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.busy_s() <= 0:
        return None
    by = trace.by_order(PROGRAM, drive.ALGORITHMS)
    if not by or by["ogasched"] <= 0:
        return None
    return 100.0 * by["ogasched"] / trace.busy_s()
