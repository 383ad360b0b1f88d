"""Pallas TPU kernel: exact in-kernel sortscan water-level projection.

This ports the PR 5 breakpoint-sweep projection
(``core.projection.project_rows_sortscan``) into the kernel so the fused
OGA step is exact on-device: g(tau) = sum_l m_l clip(z_l - tau, 0, a_l) is
piecewise linear with breakpoints {z_l - a_l, z_l}; sort them ascending
with their slope deltas (+m at z-a, -m at z), prefix-sum the deltas to the
per-segment active-lane count, walk g down segment by segment, pick the
last breakpoint ``lo`` with g(lo) >= c, and solve the bracketing segment
in closed form. As in the reference, the scan only ever SELECTS the
segment — g(lo) and the slope are recomputed directly in one O(L) pass
(``core.projection._finish_water_level``'s tail, inlined here), so scan
rounding cannot leak into the result beyond segment-tie jitter.

Mosaic has no sort, gather or scan lowering, so everything that moves data
across lanes is a lane rotation (``pltpu.roll``) masked by a 2-D lane iota:

* layout: the 2L breakpoints and their slope deltas sit side by side in a
  power-of-two lane span P; pad slots get v = NEG so they sort to the
  FRONT, where their zero deltas keep every prefix sum honest.
* sort: a bitonic network; each compare-exchange fetches the XOR-partner
  lane with two rotations and a select, and value + payload move as a
  pair, so no index gather ever materialises.
* scan: inclusive prefix sums take log2(P) rotate-and-add steps; the
  shift-by-one for segment widths is one masked rotation.

A rotation moves f32 values bit-exactly. Matmuls against 0/1 permutation
or triangular matrices would not: at the MXU's default precision they round
f32 operands to bf16, and at HIGHEST precision they overrun the default
scoped VMEM at 200 lanes. The bisect fallback (kernels.proj_bisect) stays
available as ``method="bisect"`` for A/B; this kernel is the default
(``autotune.DEFAULT_PROJ_METHOD``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

NEG = -1e30


def _roll(x, shift: int):
    """Lane i of the result holds lane i - shift of x (mod P), as jnp.roll."""
    return pltpu.roll(x, shift % x.shape[-1], 1)


def _cumsum(x, idx):
    """Inclusive prefix sum over lanes: log2(P) roll-and-add steps."""
    for e in range(x.shape[-1].bit_length() - 1):
        x = x + jnp.where(idx >= (1 << e), _roll(x, 1 << e), 0.0)
    return x


def _shift1(x, idx):
    """(shift x)_j = x_{j-1}, with 0 in lane 0."""
    return jnp.where(idx >= 1, _roll(x, 1), 0.0)


def _bitonic_sort_pairs(v, d, idx):
    """Sort lanes of v ascending, carrying payload d along — (Rb, P) each,
    P a power of two. Classic bitonic network: block size k doubles, the
    compare distance j halves within each block; a lane keeps the min of
    its partner pair iff its block direction is ascending and it is the
    lower index (or descending and upper). Both sides of a pair compute
    the same swap decision, so (value, payload) move together and ties
    leave both lanes untouched."""
    log_p = v.shape[-1].bit_length() - 1
    for kb in range(1, log_p + 1):
        k = 1 << kb
        for j in (1 << jb for jb in range(kb - 1, -1, -1)):
            # the XOR partner i ^ j is lane i + j where bit j of i is
            # clear, and lane i - j where it is set
            lower = (idx // j) % 2 == 0
            pv = jnp.where(lower, _roll(v, -j), _roll(v, j))
            pd = jnp.where(lower, _roll(d, -j), _roll(d, j))
            # keep the min iff (bit j clear) == (block ascending). Mosaic
            # cannot compare or select i1 vectors, so the bits are compared
            # as integers and the select is spelled with & | ~.
            want_min = (idx // j) % 2 == (idx // k) % 2
            swap = (want_min & (pv < v)) | (~want_min & (pv > v))
            v = jnp.where(swap, pv, v)
            d = jnp.where(swap, pd, d)
    return v, d


def _sortscan_water_level(z, a, m, c):
    """Exact water level by in-kernel breakpoint sweep.

    z, a, m: (Rb, L) f32; c: (Rb, 1) f32. Returns (tau, need): tau solves
    g(tau) = c exactly (to f32 rounding) on ``need`` rows (capacity
    binding) and is 0 elsewhere. Drop-in for proj_bisect._water_level.
    """
    rb, lp = z.shape
    p = autotune.sort_lanes(lp)

    box = jnp.clip(z, 0.0, a) * m
    s_box = jnp.sum(box, axis=1, keepdims=True)
    need = s_box > c

    # lay the 2L breakpoints + slope deltas side by side in P pow2 lanes;
    # the NEG-filled pad slots sort to the front with delta 0
    fill = [jnp.full((rb, p - 2 * lp), NEG, jnp.float32)] if p > 2 * lp else []
    zero = [jnp.zeros((rb, p - 2 * lp), jnp.float32)] if p > 2 * lp else []
    v = jnp.concatenate([z - a, z] + fill, axis=1)
    d = jnp.concatenate([m, -m] + zero, axis=1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (rb, p), 1)

    vs, ds = _bitonic_sort_pairs(v, d, idx)

    # n_seg_j = active lanes on [vs_j, vs_{j+1}); g walks down from the
    # smallest breakpoint by n_seg_{j-1} * (vs_j - vs_{j-1}) per segment.
    # Pad slots contribute width ~1e30 but slope exactly 0.
    n_seg = _cumsum(ds, idx)
    drop = _shift1(n_seg, idx) * (vs - _shift1(vs, idx))
    v0 = jnp.min(v, axis=1, keepdims=True)
    g0 = jnp.sum(jnp.clip(z - v0, 0.0, a) * m, axis=1, keepdims=True)
    gv = g0 - _cumsum(drop, idx)

    # last breakpoint on/above level c, then the exact closed-form segment
    # solve with g(lo) and the slope recomputed directly (scan rounding
    # only ever picks the segment)
    lo = jnp.max(jnp.where(gv >= c, vs, NEG), axis=1, keepdims=True)
    glo = jnp.sum(jnp.clip(z - lo, 0.0, a) * m, axis=1, keepdims=True)
    n = jnp.sum(m * (z - a <= lo) * (z > lo), axis=1, keepdims=True)
    tau = jnp.where(n > 0.5, lo + (glo - c) / jnp.maximum(n, 1.0), lo)
    tau = jnp.maximum(tau, 0.0)
    return jnp.where(need, tau, 0.0), need


def _kernel(z_ref, a_ref, mask_ref, c_ref, out_ref):
    z = z_ref[...].astype(jnp.float32)          # (Rb, L)
    a = a_ref[...].astype(jnp.float32)
    m = mask_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)[:, :1]   # (Rb, 1)

    tau, need = _sortscan_water_level(z, a, m, c)
    box = jnp.clip(z, 0.0, a) * m
    proj = jnp.clip(z - tau, 0.0, a) * m
    out_ref[...] = jnp.where(need, proj, box).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def proj_sortscan(z, a, mask, c, *, row_block=None, interpret: bool = False):
    """Exact projection of rows of z (N, L) onto {0 <= y <= a,
    sum(y * mask) <= c} — the sortscan sweep run on-device.

    a, mask: (N, L); c: (N,). ``row_block`` is the autotuned grid tile
    (``autotune.shape_rule`` when None); rows are independent, so the tile
    only sets the grid shape, never the values.
    """
    N, L = z.shape
    rb = row_block or autotune.shape_rule(N, L).row_block
    lanes = autotune.LANE_FLOOR
    pad_n = (-N) % rb
    pad_l = (-L) % lanes
    zp = jnp.pad(z, ((0, pad_n), (0, pad_l)))
    ap = jnp.pad(a, ((0, pad_n), (0, pad_l)))
    mp = jnp.pad(mask, ((0, pad_n), (0, pad_l)))
    cp = jnp.pad(c, (0, pad_n))[:, None] * jnp.ones((1, lanes), z.dtype)
    Np, Lp = zp.shape
    row_spec = pl.BlockSpec((rb, Lp), lambda i: (i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(Np // rb,),
        in_specs=[
            row_spec,
            row_spec,
            row_spec,
            pl.BlockSpec((rb, lanes), lambda i: (i, 0)),
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((Np, Lp), z.dtype),
        interpret=interpret,
        name="proj_sortscan",
    )(zp, ap, mp, cp)
    return out[:N, :L]
