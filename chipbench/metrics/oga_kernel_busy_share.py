"""Device time of the fused OGA kernel, the ops named ``oga_step_fused``
(the name ``kernels/oga_step.py`` gives its ``pallas_call``), as a share of
the device's busy time: the union of the kernel's op intervals on each chip
in the window, averaged over the chips, over busy time."""
import re

import numpy as np

import scopes

KERNEL = re.compile(r"^oga_step_fused(\.\d+)?$")


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.busy_s() <= 0:
        return None
    codes = [i for i, n in enumerate(trace.op_names) if KERNEL.match(n)]
    if not codes:
        return None
    t = scopes.union_s(trace.cols, np.isin(trace.cols["name"], codes),
                       trace.lo, trace.hi, trace.devices)
    return 100.0 * t / trace.busy_s()
