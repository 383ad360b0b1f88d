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
import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import baselines, graph
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


# ---------------------------------------- the budgeted heuristics' port loop

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) .*\{$")
_OPCODE = re.compile(r"^\s*(?:ROOT )?%?[\w.-]+ = .*?\b([a-z][\w-]*)\(")
_CALLED = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.-]+)")


def _computations(text):
    """HLO text -> {computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if cur is None and m:
            cur = m.group(1)
            comps[cur] = []
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


def _opcodes(comps, root):
    """Opcodes of ``root`` and every computation it calls, fusions too."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += _CALLED.findall(line)
    return collections.Counter(
        m.group(1) for n in seen for m in map(_OPCODE.match, comps[n]) if m)


def _innermost_loop_bodies(text):
    comps = _computations(text)
    bodies = {b for lines in comps.values() for line in lines
              for b in re.findall(r"body=%?([\w.-]+)", line)}
    ops = {b: _opcodes(comps, b) for b in bodies}
    return {b: o for b, o in ops.items() if not o["while"]}


def _spec_shapes(sharding, L, R, K, G=None):
    lead = () if G is None else (G,)

    def f(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)

    return graph.ClusterSpec(mask=f(L, R), a=f(L, K), c=f(R, K),
                             alpha=f(R, K), beta=f(K),
                             kinds=f(K, dtype=jnp.int32))


@pytest.mark.parametrize("name", ["drf", "binpacking"])
@pytest.mark.parametrize("shape", ["fig5", "tab2"])
def test_port_loop_moves_no_data_for_v5e(one_chip, name, shape):
    """The port loop's body, fused computations included, holds no sort,
    gather, scatter or cumsum: a sort over R nodes and per-element
    permutations there ran close to one index at a time on the chip."""
    T = 500
    if shape == "fig5":  # baselines.run on one deployment, as run_all does
        fn = baselines.run
        args = (_spec_shapes(one_chip, FIG5_L, 1024, 6),
                jax.ShapeDtypeStruct((T, FIG5_L), jnp.float32,
                                     sharding=one_chip))
    else:  # a 64-point sweep chunk under vmap
        fn = baselines.run_batch
        args = (_spec_shapes(one_chip, TAB2_L, 128, 6, G=64),
                jax.ShapeDtypeStruct((64, T, TAB2_L), jnp.float32,
                                     sharding=one_chip))
    text = jax.jit(lambda s, a: fn(s, a, name)).lower(*args).compile().as_text()
    bodies = _innermost_loop_bodies(text)
    assert bodies
    for body, ops in bodies.items():
        assert ops["dynamic-update-slice"], body
        moved = {op: ops[op] for op in
                 ("sort", "gather", "scatter", "reduce-window") if ops[op]}
        assert not moved, (body, moved)
