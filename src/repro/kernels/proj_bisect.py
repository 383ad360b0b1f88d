"""Pallas TPU kernel: batched box-capped simplex projection (paper Alg. 1).

One grid row-block projects a tile of (r, k) cells; each cell's row holds its
L_r channel entries. The paper's sort + data-dependent repeat loop is
replaced by branch-free bisection on the water level tau (DESIGN.md §3):
pure VPU arithmetic per lane — no sorting network, no data-dependent trip
counts, identical control flow for every cell. Since the sortscan sweep
landed in-kernel (kernels.sortscan) this bisection is no longer the fused
default — it stays behind ``method="bisect"`` as the A/B baseline and as
the low-VMEM fallback shape the autotuner may still pick.

The bracket is seeded rather than started at [0, max z]: g is 1-Lipschitz
per active lane, so tau* >= (sum(box) - c) / n_active, and the default
iteration count drops from 64 to ``autotune.DEFAULT_BISECT_ITERS``. A
final secant step closes most of the remaining gap: g is piecewise linear,
so the chord from (lo, g(lo)) to (hi, g(hi)) crosses c exactly at tau*
once the bracket is breakpoint-free (the common case after the halvings).
When a kink remains inside the bracket the chord can land on either side
of tau* — g is NOT convex (each clip term has slope 0 -> -1 -> 0, a
concave kink at z_l - a_l) — so the hard accuracy/feasibility guarantee is
the bracket width itself: |tau - tau*| <= (hi0 - lo0) / 2^iters, i.e.
capacity overshoot at most n_active * that (f32-rounding magnitude at the
scales this scheduler runs; pinned vs the exact oracle in
tests/test_kernels.py). ``iters`` is an autotuned knob (autotune.BISECT_ITERS).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune

# Back-compat alias: the number itself lives in kernels.autotune (the
# hardcoded-tiling lint rule keeps it there).
ITERS = autotune.DEFAULT_BISECT_ITERS
NEG = -1e30


def _water_level(z, a, m, c, iters: int = ITERS):
    """Shared bisection body: seeded bracket, ``iters`` halvings, secant
    finish.

    z, a, m: (Rb, L) f32; c: (Rb, 1) f32. Returns (tau, need) with tau the
    water level on `need` rows (capacity binding) and 0 elsewhere.
    """
    box = jnp.clip(z, 0.0, a) * m
    s_box = jnp.sum(box, axis=1, keepdims=True)
    need = s_box > c

    n_act = jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0)
    lo = jnp.maximum((s_box - c) / n_act, 0.0)  # g(lo) >= c (1-Lipschitz/lane)
    hi = jnp.maximum(jnp.max(jnp.where(m > 0, z, NEG), axis=1, keepdims=True), lo)

    def g(tau):
        return jnp.sum(jnp.clip(z - tau, 0.0, a) * m, axis=1, keepdims=True)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        too_big = g(mid) > c
        return jnp.where(too_big, mid, lo), jnp.where(too_big, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    glo, ghi = g(lo), g(hi)
    tau = lo + (glo - c) * (hi - lo) / jnp.maximum(glo - ghi, 1e-30)
    tau = jnp.clip(tau, lo, hi)
    return jnp.where(need, tau, 0.0), need


def _kernel(z_ref, a_ref, mask_ref, c_ref, out_ref, *, iters: int):
    z = z_ref[...].astype(jnp.float32)          # (Rb, L)
    a = a_ref[...].astype(jnp.float32)
    m = mask_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)[:, :1]   # (Rb, 1)

    tau, need = _water_level(z, a, m, c, iters=iters)
    box = jnp.clip(z, 0.0, a) * m
    proj = jnp.clip(z - tau, 0.0, a) * m
    out_ref[...] = jnp.where(need, proj, box).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("row_block", "iters", "interpret")
)
def proj_bisect(
    z, a, mask, c, *, row_block=None, iters=None, interpret: bool = False
):
    """Project rows of z (N, L) onto {0 <= y <= a, sum(y * mask) <= c}.

    a, mask: (N, L); c: (N,). Rows are independent — the paper's per-(r,k)
    parallelism maps to the Pallas grid. ``row_block``/``iters`` are the
    autotuned knobs (``autotune.shape_rule`` and the default iteration
    count when None).
    """
    N, L = z.shape
    rb = row_block or autotune.shape_rule(N, L).row_block
    it = iters or autotune.DEFAULT_BISECT_ITERS
    lanes = autotune.LANE_FLOOR
    pad_n = (-N) % rb
    pad_l = (-L) % lanes  # TPU lane alignment
    zp = jnp.pad(z, ((0, pad_n), (0, pad_l)))
    ap = jnp.pad(a, ((0, pad_n), (0, pad_l)))
    mp = jnp.pad(mask, ((0, pad_n), (0, pad_l)))
    cp = jnp.pad(c, (0, pad_n))[:, None] * jnp.ones((1, lanes), z.dtype)
    Np, Lp = zp.shape
    grid = (Np // rb,)
    row_spec = pl.BlockSpec((rb, Lp), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, iters=it),
        grid=grid,
        in_specs=[
            row_spec,
            row_spec,
            row_spec,
            pl.BlockSpec((rb, lanes), lambda i: (i, 0)),
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((Np, Lp), z.dtype),
        interpret=interpret,
        name="proj_bisect",
    )(zp, ap, mp, cp)
    return out[:N, :L]
