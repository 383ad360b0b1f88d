"""The work of one OGASched decision, counted from the deployment's shape.

One decision is one slot of the paper's Alg. 1 for one deployment of
L ports, R instances and K resource types: the reward q(x, y) (eq. 7-8),
its gradient (eq. 30), the ascent step and the exact projection of every
(r, k) cell onto {0 <= y_l <= a_l^k, sum_l y_l <= c_r^k} (eq. 32).

The count depends on (L, R, K) alone. It charges every operand once at its
unpadded size, and each cell's projection as one sort-based exact
projection of its L lanes, whatever implements the step (a fused kernel,
separate XLA passes, another projection method, any tiling or lane
packing). So a change of implementation moves the time of the step and
never this count.
"""
from __future__ import annotations

import math

F32 = 4  # bytes: the deployment states float32 decisions


def oga_decision_bytes(L: int, R: int, K: int) -> int:
    """Bytes one decision must move at least: y read and y(t+1) written,
    the static operands a (L, K), mask (L, R), c, alpha (R, K), beta and
    the utility kinds (K,), the arrivals x (L,), and the reward."""
    words = 2 * L * R * K + L * R + L * K + 2 * R * K + 2 * K + L + 1
    return F32 * words


def oga_decision_flops(L: int, R: int, K: int) -> int:
    """Operations of one decision. Per (l, r, k) element: utility value and
    its reduction (5), gradient with the k* penalty and masking (6), ascent
    (2), box clamp and water-filling (5); per cell a sort of its 2L
    breakpoints, 2L log2(2L) comparisons, and two prefix sums over them."""
    elem = L * R * K
    per_cell = 2 * L * math.log2(2 * L) + 2 * (2 * L)
    return int(round(18 * elem + R * K * per_cell))


def oga_work(L: int, R: int, K: int, decisions: int) -> dict:
    """{"bytes", "flops"} of ``decisions`` OGASched decisions."""
    return {
        "bytes": decisions * oga_decision_bytes(L, R, K),
        "flops": decisions * oga_decision_flops(L, R, K),
    }


def roofline_share(work: dict, seconds: float, peak: dict) -> tuple[float, str]:
    """(percent of the roofline, the binding roof): the least time the chip
    could take for ``work``, the larger of its bytes over HBM bandwidth and
    its operations over the flop peak, as a share of ``seconds``."""
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_flops = work["flops"] / peak["flops_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_bytes, t_flops) / seconds, bound
