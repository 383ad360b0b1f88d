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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, oga_step, sortscan

# Tab. 2 deployment (R=128, K=6) over a 64-point sweep chunk: G*R*K rows
TAB2_ROWS, TAB2_L = 64 * 128 * 6, 10
WIDE_ROWS, WIDE_L = 4096, 200
ROW_BLOCK = autotune.DEFAULT_ROW_BLOCK  # 8, what dispatch runs


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


@pytest.mark.parametrize("method", ["sortscan", "bisect"])
def test_oga_step_fused_compiles_for_v5e(one_chip, method):
    args = _shapes(one_chip, TAB2_ROWS, *[TAB2_L] * 5, oga_step.NUM_SCAL)
    _assert_mosaic(
        lambda *o: oga_step.oga_step_fused(
            *o, method=method, row_block=ROW_BLOCK),
        args,
    )


def test_proj_sortscan_wide_compiles_for_v5e(one_chip):
    args = _shapes(one_chip, WIDE_ROWS, WIDE_L, WIDE_L, WIDE_L, 0)
    _assert_mosaic(
        lambda *o: sortscan.proj_sortscan(*o, row_block=ROW_BLOCK), args
    )
