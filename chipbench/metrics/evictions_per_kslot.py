"""Evictions per 1,000 scenario-slots in the window, summed over the
algorithms: the program's own counter (the ``evictions`` of each
deployment's ``summarize_lifecycle`` summary, summed by the lifecycle
driver) over the window's deployments times their slots."""


def read(ctx):
    stats = ctx["stats"]
    if "evictions" not in stats or not stats.get("configs"):
        return None
    return 1000.0 * stats["evictions"] / (stats["configs"]
                                          * ctx["config"]["T"])
