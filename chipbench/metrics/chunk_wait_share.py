"""Share of the window in which the sweep driver waited on its chunk
pipeline: the program's own counter, run_grid_stream(stats=...)
["chunk_wait_s"], over the window."""


def read(ctx):
    stats = ctx["stats"]
    if "chunk_wait_s" not in stats or stats.get("window_s", 0) <= 0:
        return None
    return 100.0 * stats["chunk_wait_s"] / stats["window_s"]
