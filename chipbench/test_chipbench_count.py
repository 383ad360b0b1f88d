"""The OGASched work count and the peaks table."""
import numpy as np
import pytest

import peaks
import workcount
from repro.kernels import autotune, oga_step, ops, ref

# (L, R, K) -> (bytes, flops) of one decision
PINNED = {"tab2": ((10, 128, 6), 73_036, 235_345),
          "fig5": ((100, 1024, 6), 5_376_804, 22_909_570)}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_count_pinned(name):
    shape, nbytes, flops = PINNED[name]
    assert workcount.oga_decision_bytes(*shape) == nbytes
    assert workcount.oga_decision_flops(*shape) == flops
    assert workcount.oga_work(*shape, 3) == {"bytes": 3 * nbytes,
                                             "flops": 3 * flops}


def _step_operands(L, R, K, seed=0):
    rng = np.random.default_rng(seed)
    n = R * K
    y = rng.uniform(0, 3, (n, L)).astype(np.float32)
    a = rng.uniform(0.1, 4, (n, L)).astype(np.float32)
    mask = (rng.uniform(size=(n, L)) < 0.8).astype(np.float32)
    x = (rng.uniform(size=(n, L)) < 0.7).astype(np.float32)
    kstar = (rng.uniform(size=(n, L)) < 0.2).astype(np.float32)
    scal = oga_step.pack_scal(
        np.full(n, 1.2, np.float32), np.full(n, 0.4, np.float32),
        rng.uniform(0.5, 8, n).astype(np.float32),
        (np.arange(n) % 4).astype(np.float32), np.full(n, 5.0, np.float32))
    return y, a, mask, x, kstar, scal


IMPLEMENTATIONS = {
    "reference_rows": lambda *a: ref.oga_step_ref(*a),
    "fused_sortscan_rb8": lambda *a: ops.oga_step_fused(
        *a, use_pallas=True,
        tiling=autotune.KernelConfig(row_block=8, method="sortscan")),
    "fused_sortscan_rb16": lambda *a: ops.oga_step_fused(
        *a, use_pallas=True,
        tiling=autotune.KernelConfig(row_block=16, method="sortscan")),
    "fused_bisect_rb8": lambda *a: ops.oga_step_fused(
        *a, use_pallas=True,
        tiling=autotune.KernelConfig(row_block=8, method="bisect", iters=40)),
}


def test_work_count_is_the_same_whatever_implements_the_step():
    """Four implementations of one OGA step (packed-row XLA, the Pallas
    kernel with the exact sortscan at two row blocks, with bisection) give
    the same decision, and the work the roofline charges for it, counted
    from the deployment's (L, R, K), is one number for all of them."""
    L, R, K = 10, 4, 4
    args = _step_operands(L, R, K)
    outs = {n: np.asarray(f(*args)) for n, f in IMPLEMENTATIONS.items()}
    for name, out in outs.items():
        np.testing.assert_allclose(out, outs["reference_rows"], atol=1e-4,
                                   err_msg=name)
    counts = {workcount.oga_decision_bytes(L, out.shape[0] // K, K)
              for out in outs.values()}
    assert counts == {workcount.oga_decision_bytes(L, R, K)}


def test_roofline_share_takes_the_binding_roof():
    peak = peaks.peaks("TPU v5 lite")
    work = workcount.oga_work(10, 128, 6, 64 * 500)
    share, bound = workcount.roofline_share(work, 1.0, peak)
    assert bound == "bytes"
    assert share == pytest.approx(100 * work["bytes"] / 819e9)
    half, _ = workcount.roofline_share(work, 2.0, peak)
    assert half == pytest.approx(share / 2)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
