"""The program's jitted programs that the per-layer readers attribute device
time to, by XLA module name as the TPU trace names them.

The streamed sweep runs one ``jit__grid_ogasched`` program (OGASched over a
chunk) and one ``jit_run_batch`` program per heuristic. ``simulator.run_all``
runs one ``jit_run`` program per algorithm, one after the other, in the
order of ``RUN_ALL_ORDER``; each benchmark span ``call`` holds one such
call. A program renamed by a later change is found by no pattern here: its
readers then return nothing, and the metric drops out of the line.
"""
SWEEP_OGA = r"^jit__grid_ogasched$"
SWEEP_BASELINES = r"^jit_run_batch$"
RUN_ALL = r"^jit_run$"
RUN_ALL_ORDER = ("ogasched", "drf", "fairness", "binpacking", "spreading")


def oga_seconds(trace) -> float:
    """Device seconds of OGASched's programs in the window."""
    t = trace.program_s([SWEEP_OGA])
    if t > 0:
        return t
    by = trace.by_order(RUN_ALL, RUN_ALL_ORDER)
    return by["ogasched"] if by else 0.0


def baselines_seconds(trace) -> float:
    """Device seconds of the four heuristics' programs in the window."""
    t = trace.program_s([SWEEP_BASELINES])
    if t > 0:
        return t
    by = trace.by_order(RUN_ALL, RUN_ALL_ORDER)
    return sum(v for k, v in by.items() if k != "ogasched") if by else 0.0
