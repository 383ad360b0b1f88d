"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler lowers each kernel for a v5e chip that is
described, not attached, and raises what the chip's compiler would raise
(Mosaic lowering gaps, VMEM overruns, unaligned tiles). Interpret-mode tests
cannot see those. Each compile asserts that the Mosaic kernel
(``tpu_custom_call``) is in the program, so no jnp fallback passes.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU compiler's library, and every
test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, oga_step, proj_bisect, sortscan

# Tab. 2 deployment (R=128, K=6) over a 64-point sweep chunk: G*R*K rows
TAB2_ROWS, TAB2_L = 64 * 128 * 6, 10
# Fig. 5 deployment (R=1024, K=6), 100 job types: R*K rows
FIG5_ROWS, FIG5_L = 1024 * 6, 100
WIDE_ROWS, WIDE_L = 4096, 200


def _row_block(kernel, n, l):
    """The row block dispatch runs at this packed shape."""
    return autotune.resolve(kernel, n, l).row_block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # no skip: without the TPU compiler this test fails, it does not pass
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, n, *widths):
    return [jax.ShapeDtypeStruct((n, w) if w else (n,), jnp.float32,
                                 sharding=sharding) for w in widths]


def _assert_mosaic(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("method,rows,lanes", [
    ("sortscan", TAB2_ROWS, TAB2_L),
    ("bisect", TAB2_ROWS, TAB2_L),
    ("sortscan", FIG5_ROWS, FIG5_L),
], ids=["sortscan", "bisect", "sortscan-fig5"])
def test_oga_step_fused_compiles_for_v5e(one_chip, method, rows, lanes):
    """At the tile dispatch resolves: a VMEM overrun there fails here."""
    args = _shapes(one_chip, rows, *[lanes] * 5, oga_step.NUM_SCAL)
    rb = _row_block("oga_step", rows, lanes)
    _assert_mosaic(
        lambda *o: oga_step.oga_step_fused(*o, method=method, row_block=rb),
        args,
    )


def test_proj_sortscan_wide_compiles_for_v5e(one_chip):
    args = _shapes(one_chip, WIDE_ROWS, WIDE_L, WIDE_L, WIDE_L, 0)
    rb = _row_block("proj", WIDE_ROWS, WIDE_L)
    _assert_mosaic(lambda *o: sortscan.proj_sortscan(*o, row_block=rb), args)


# each kernel by the name its pallas_call gives it: its function, unjitted,
# and the widths of its operands
KERNELS = {
    "oga_step_fused": (oga_step.oga_step_fused,
                       [TAB2_L] * 5 + [oga_step.NUM_SCAL]),
    "proj_sortscan": (sortscan.proj_sortscan, [WIDE_L] * 3 + [0]),
    "proj_bisect": (proj_bisect.proj_bisect, [WIDE_L] * 3 + [0]),
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_op_keeps_its_name_under_any_caller(one_chip, kernel):
    """The TPU op of a kernel is named by its pallas_call's ``name``, not
    by the function that calls it: the name a trace reader looks for."""
    fn, widths = KERNELS[kernel]
    rows = 768 if kernel == "oga_step_fused" else WIDE_ROWS
    rb = _row_block("oga_step" if kernel == "oga_step_fused" else "proj",
                    rows, widths[0])

    def renamed_caller(*o):
        return fn.__wrapped__(*o, row_block=rb)

    text = jax.jit(renamed_caller).lower(
        *_shapes(one_chip, rows, *widths)).compile().as_text()
    ops = re.findall(r"%([\w.-]+) = \S+ custom-call\([^\n]*"
                     r'custom_call_target="tpu_custom_call"', text)
    assert ops and all(re.fullmatch(rf"{kernel}(\.\d+)?", op) for op in ops)
