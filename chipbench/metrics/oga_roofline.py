"""OGASched's share of its roofline: the least time the chip could take for
the decisions made in the window (workcount.py, from the deployment's
shape alone), over the device time of the OGASched programs (programs.py)."""
import peaks
import programs
import workcount


def read(ctx):
    trace, stats = ctx["trace"], ctx["stats"]
    if trace is None or not stats.get("oga_decisions"):
        return None
    seconds = programs.oga_seconds(trace)
    if seconds <= 0:
        return None
    chips = ctx["device"]["count"]
    work = workcount.oga_work(*stats["oga_shape"], stats["oga_decisions"])
    share, _ = workcount.roofline_share(
        {k: v / chips for k, v in work.items()}, seconds,
        peaks.peaks(ctx["device"]["kind"]))
    return share
