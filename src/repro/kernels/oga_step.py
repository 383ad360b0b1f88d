"""Pallas TPU kernel: fused OGA slot update (beyond-paper optimisation).

Fuses reward gradient (eq. 30) + ascent + projection for a tile of
(r, k) cells in one VMEM pass: y is read once and y(t+1) written once,
instead of three HBM round-trips (grad kernel, axpy, projection). The OGA
update is memory-bound (O(1) flops/byte), so fusion is the dominant lever —
recorded in EXPERIMENTS.md §Perf (scheduler kernel iterations).

Row layout: row n = cell (r, k) with L lanes (ports). Per-row scalars are
packed as the columns of ``scal`` — ``SCAL_COLUMNS`` below is the single
definition of that layout (kernels.ops builds it, kernels.ref unpacks it).

The projection is selected statically per call: ``method="sortscan"``
(default) runs the exact in-kernel breakpoint sweep
(kernels.sortscan._sortscan_water_level — same closed-form solve as the
off-TPU production path, so the fused step is exact on-device), while
``method="bisect"`` keeps the seeded-bracket bisection + secant finish
shared with kernels.proj_bisect as the A/B baseline. Tiling (``row_block``)
and the bisect iteration count come from kernels.autotune.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune
from repro.kernels.proj_bisect import _water_level
from repro.kernels.sortscan import _sortscan_water_level

# The packed-scalar operand layout, column by column. scal[:, i] holds
# SCAL_COLUMNS[i]; columns past NUM_SCAL are zero padding up to the TPU lane
# width (asserted in oga_step_fused).
SCAL_COLUMNS = ("alpha", "beta", "c", "kind", "eta")
NUM_SCAL = len(SCAL_COLUMNS)
_SCAL_LANES = autotune.SCAL_LANES


def pack_scal_static(alpha, beta, c, kind) -> jax.Array:
    """Stack the static per-row scalars (N,) each into the leading
    (N, NUM_SCAL - 1) columns of the kernel operand — everything in
    ``SCAL_COLUMNS`` except eta, which decays per step and is appended by
    ``with_eta``. This pair is the ONLY place the layout is constructed."""
    return jnp.stack([alpha, beta, c, kind], axis=1)


def with_eta(scal_static, eta) -> jax.Array:
    """Append the eta column to ``pack_scal_static`` output: ``eta`` may be
    a scalar (one config) or per-row (N,) (grid-flattened chunks)."""
    n = scal_static.shape[0]
    eta_col = jnp.broadcast_to(jnp.asarray(eta, scal_static.dtype), (n,))
    return jnp.concatenate([scal_static, eta_col[:, None]], axis=1)


def pack_scal(alpha, beta, c, kind, eta) -> jax.Array:
    """The full (N, NUM_SCAL) kernel operand in ``SCAL_COLUMNS`` order."""
    return with_eta(pack_scal_static(alpha, beta, c, kind), eta)


def _util_grad(kind, alpha, y):
    y = jnp.maximum(y, 0.0)  # utilities are defined on R_{>=0} (eq. 51)
    g_lin = alpha
    g_log = alpha / (1.0 + y)
    g_rec = 1.0 / jnp.square(y + alpha)
    g_pol = alpha / (2.0 * jnp.sqrt(y + 1.0))
    g = jnp.where(kind == 0, g_lin, 0.0)
    g = jnp.where(kind == 1, g_log, g)
    g = jnp.where(kind == 2, g_rec, g)
    return jnp.where(kind == 3, g_pol, g)


def _kernel(
    y_ref, a_ref, mask_ref, x_ref, kstar_ref, scal_ref, out_ref,
    *, method: str, iters: int
):
    y = y_ref[...].astype(jnp.float32)          # (Rb, L)
    a = a_ref[...].astype(jnp.float32)
    m = mask_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)          # (Rb, L) arrivals (bcast rows)
    kst = kstar_ref[...].astype(jnp.float32)    # (Rb, L) 1{k = k*_l}
    scal = scal_ref[...].astype(jnp.float32)    # (Rb, lanes): SCAL_COLUMNS
    alpha = scal[:, 0:1]
    beta = scal[:, 1:2]
    c = scal[:, 2:3]
    kind = scal[:, 3:4]
    eta = scal[:, 4:5]

    # eq. 30 gradient, ascent step
    g = _util_grad(kind, alpha, y * m) - beta * kst
    z = y + eta * x * g * m

    # projection: exact sortscan sweep by default; seeded bisect for A/B
    if method == "sortscan":
        tau, need = _sortscan_water_level(z, a, m, c)
    else:
        tau, need = _water_level(z, a, m, c, iters=iters)
    box = jnp.clip(z, 0.0, a) * m
    proj = jnp.clip(z - tau, 0.0, a) * m
    out_ref[...] = jnp.where(need, proj, box).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("method", "row_block", "iters", "interpret")
)
def oga_step_fused(
    y, a, mask, x, kstar, scal, *,
    method: str = None, row_block=None, iters=None, interpret: bool = False,
):
    """Fused OGA slot update over (N, L) rows — N is R*K for one config, or
    G*R*K when a sweep chunk's grid axis is flattened in (kernels.ops.
    oga_update_batch issues exactly one such call per step for a whole
    chunk).

    y, a, mask, x, kstar: (N, L). scal: (N, NUM_SCAL) per ``SCAL_COLUMNS``.
    method/row_block/iters are the autotuned knobs (when None: sortscan,
    ``autotune.shape_rule``'s row block, the default iteration count;
    ``iters`` applies to method="bisect" only).
    Returns y(t+1) (N, L).
    """
    meth = method or autotune.DEFAULT_PROJ_METHOD
    if meth not in autotune.PROJ_METHODS:
        raise ValueError(
            f"method must be in {autotune.PROJ_METHODS}, got {meth!r}"
        )
    N, L = y.shape
    rb = row_block or autotune.shape_rule(N, L).row_block
    it = iters or autotune.DEFAULT_BISECT_ITERS
    if scal.shape[1] > _SCAL_LANES:
        raise ValueError(
            f"scal has {scal.shape[1]} columns; the kernel packs them into "
            f"one {_SCAL_LANES}-lane block (layout {SCAL_COLUMNS})"
        )
    pad_n = (-N) % rb
    pad_l = (-L) % autotune.LANE_FLOOR
    pad2 = lambda t: jnp.pad(t, ((0, pad_n), (0, pad_l)))
    yp, ap, mp, xp, kp = map(pad2, (y, a, mask, x, kstar))
    sp = jnp.pad(scal, ((0, pad_n), (0, _SCAL_LANES - scal.shape[1])))
    Np, Lp = yp.shape
    row_spec = pl.BlockSpec((rb, Lp), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, method=meth, iters=it),
        grid=(Np // rb,),
        in_specs=[row_spec] * 5
        + [pl.BlockSpec((rb, _SCAL_LANES), lambda i: (i, 0))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((Np, Lp), y.dtype),
        interpret=interpret,
        name="oga_step_fused",
    )(yp, ap, mp, xp, kp, sp)
    return out[:N, :L]
