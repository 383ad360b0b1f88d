"""Jit'd dispatch wrappers: Pallas on TPU, pure-jnp packed rows elsewhere,
with the bisection oracle available for A/B (config flag
``use_pallas_kernels``).

Also home of the spec-level OGA backend switch (``oga_update_spec``), its
grid-flattened batch variant (``oga_update_batch`` — one kernel call per
step for a whole sweep chunk, rows N = G*R*K), and the (L, R, K) <->
(N = R*K, L) row-layout converters the fused kernel needs: row n = cell
(r, k), lanes = ports. Packing is a transpose + reshape, so the round-trip
is exact. The packed-scalar column layout is defined once, in
``kernels.oga_step.SCAL_COLUMNS``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import projection as _projection
from repro.core import reward as _reward
from repro.kernels import autotune as _at
from repro.kernels import flash_attention as _fa
from repro.kernels import oga_step as _og
from repro.kernels import proj_bisect as _pb
from repro.kernels import ref as _ref
from repro.kernels import sortscan as _ss

OGA_BACKENDS = ("auto", "fused", "reference")


@functools.lru_cache(maxsize=1)
def _platform() -> str:
    """The default backend platform, resolved ONCE per process — dispatch
    runs per kernel call, and querying the device registry each time is
    measurable overhead on the hot path."""
    return jax.default_backend()


def _on_tpu() -> bool:
    return _platform() == "tpu"


def resolve_oga_backend(backend: str = "auto") -> str:
    """"auto" -> "fused" everywhere: real Pallas on TPU, the packed-row jnp
    path with the exact sorted projection elsewhere (kernels.ref.oga_step_ref
    — same data layout, no Pallas interpreter, vmappable)."""
    if backend not in OGA_BACKENDS:
        raise ValueError(f"backend must be one of {OGA_BACKENDS}, got {backend!r}")
    if backend == "auto":
        return "fused"
    return backend


def backend_provenance(backend: str = "auto") -> dict:
    """What actually runs for ``backend`` on this process — recorded into
    BENCH_kernels.json rows so "auto" results are unambiguous about the
    path measured."""
    resolved = resolve_oga_backend(backend)
    fused_impl = "pallas" if _on_tpu() else "jnp-rows"
    return {
        "backend_requested": backend,
        "backend_resolved": resolved,
        "platform": _platform(),
        "device_kind": jax.devices()[0].device_kind,
        "fused_impl": fused_impl if resolved == "fused" else "spec-level",
    }


# ------------------------------------------------------------- row layout --
def pack_rows(t: jax.Array) -> jax.Array:
    """(L, R, K) decision tensor -> (R*K, L) kernel rows."""
    L, R, K = t.shape
    return t.transpose(1, 2, 0).reshape(R * K, L)


def unpack_rows(rows: jax.Array, L: int, R: int, K: int) -> jax.Array:
    """(R*K, L) kernel rows -> (L, R, K) decision tensor."""
    return rows.reshape(R, K, L).transpose(2, 0, 1)


def pack_spec_operands(spec):
    """Static fused-kernel operands for a ClusterSpec.

    Returns (a_rows, mask_rows, scal_static): per-row channel caps and
    adjacency (N, L), plus the leading static columns of the kernel's
    packed-scalar operand (N, NUM_SCAL - 1) in ``oga_step.SCAL_COLUMNS``
    order — eta is appended per step since it decays. Build once per
    trajectory (ogasched.run / lifecycle.run hoist it out of their scan
    bodies) and thread through ``operands=``.
    """
    L, R, K = spec.L, spec.R, spec.K
    a_rows = jnp.broadcast_to(spec.a.T[None], (R, K, L)).reshape(R * K, L)
    mask_rows = jnp.broadcast_to(spec.mask.T[:, None], (R, K, L)).reshape(R * K, L)
    scal_static = _og.pack_scal_static(
        spec.alpha.reshape(-1),
        jnp.broadcast_to(spec.beta[None], (R, K)).reshape(-1),
        spec.c.reshape(-1),
        jnp.broadcast_to(spec.kinds[None], (R, K)).reshape(-1).astype(spec.a.dtype),
    )
    return a_rows, mask_rows, scal_static


def pack_spec_operands_batch(spec):
    """``pack_spec_operands`` for a stacked spec (every leaf leading (G,)),
    with the grid axis flattened into the row axis: (G*R*K, L) / (G*N, 4)."""
    a_rows, mask_rows, scal_static = jax.vmap(pack_spec_operands)(spec)
    flat = lambda t: t.reshape((-1,) + t.shape[2:])
    return flat(a_rows), flat(mask_rows), flat(scal_static)


def _kstar_rows(spec, y):
    """1{k = k*_l} rows for one config: k*_l = argmax_k beta_k sum_r y (eq.
    27), same first-index tie rule as reward_grad, broadcast to (R*K, L)."""
    L, R, K = spec.L, spec.R, spec.K
    s = jnp.sum(y * spec.mask[:, :, None], axis=1)  # (L, K)
    kstar = jax.nn.one_hot(jnp.argmax(spec.beta[None] * s, axis=1), K, dtype=y.dtype)
    return jnp.broadcast_to(kstar.T[None], (R, K, L)).reshape(R * K, L)


def _dispatch_fused(y_rows, a_rows, mask_rows, x_rows, kstar_rows, scal,
                    use_pallas, tiling=None):
    """Pallas on TPU, packed-row jnp (exact sorted projection) elsewhere.
    ``use_pallas`` forces: True -> Pallas (interpret mode off-TPU, slow —
    kernel correctness checks only), False -> jnp rows.

    ``tiling`` (an ``autotune.KernelConfig``) pins the Pallas tiling; when
    None it resolves from the autotune cache on the static packed shape —
    winner if warmed, ``autotune.shape_rule``'s row block on a miss.
    Production dispatch is value-deterministic: only the exact sortscan
    method runs here regardless of what the cache holds (a bisect entry
    contributes its row_block only — bisect output depends on its
    iteration count, and cache state must never change values, only
    speed). Explicit bisect A/B goes through
    ``ops.oga_step_fused(tiling=...)``.
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        cfg = tiling
        if cfg is None:
            cfg = _at.resolve("oga_step", *y_rows.shape)
        if cfg.method != "sortscan":
            cfg = cfg._replace(method="sortscan")
        return _og.oga_step_fused(
            y_rows, a_rows, mask_rows, x_rows, kstar_rows, scal,
            method=cfg.method, row_block=cfg.row_block, iters=cfg.iters or None,
            interpret=not _on_tpu(),
        )
    return _ref.oga_step_ref(y_rows, a_rows, mask_rows, x_rows, kstar_rows, scal)


def oga_update_spec(
    spec,
    y: jax.Array,
    x: jax.Array,
    eta: jax.Array,
    *,
    backend: str = "auto",
    operands=None,
    use_pallas: bool | None = None,
    tiling=None,
) -> jax.Array:
    """One OGA slot update y -> y(t+1) at the (L, R, K) spec level.

    backend:
      "reference" — grad (eq. 30), ascent, spec-level exact projection as
                    separate (L, R, K) passes. Both backends project
                    exactly now; the historical bisection A/B lives at the
                    projection level (``projection.project(method="bisect",
                    iters=...)``).
      "fused"     — the single-pass packed-row path over (R*K, L) rows:
                    real Pallas on TPU, the jnp rows implementation with the
                    exact sorted projection elsewhere.
      "auto"      — "fused".

    ``operands`` optionally carries ``pack_spec_operands(spec)`` so a scan
    body does not rebuild the static rows every step. ``use_pallas`` forces
    the fused dispatch (True: Pallas even off-TPU in interpret mode; False:
    jnp rows even on TPU); default picks by platform. ``tiling`` pins the
    Pallas tiling (``autotune.KernelConfig``; default: autotune cache).
    """
    backend = resolve_oga_backend(backend)
    if backend == "reference":
        g = _reward.reward_grad(spec, x, y)
        return _projection.project(spec, y + eta * g)

    L, R, K = spec.L, spec.R, spec.K
    a_rows, mask_rows, scal_static = (
        pack_spec_operands(spec) if operands is None else operands
    )
    y_rows = pack_rows(y)
    kstar_rows = _kstar_rows(spec, y)
    x_rows = jnp.broadcast_to(x.astype(y.dtype)[None], (R * K, L))
    scal = _og.with_eta(scal_static, eta)
    rows = _dispatch_fused(
        y_rows, a_rows, mask_rows, x_rows, kstar_rows, scal, use_pallas,
        tiling=tiling,
    )
    return unpack_rows(rows, L, R, K)


def oga_update_batch(
    spec,
    y: jax.Array,
    x: jax.Array,
    eta: jax.Array,
    *,
    operands=None,
    use_pallas: bool | None = None,
    tiling=None,
) -> jax.Array:
    """One fused OGA slot update for a whole stacked grid of G configs.

    The grid axis is flattened into the kernel's row axis — N = G*R*K rows,
    ONE kernel dispatch per step for the entire chunk — instead of vmapping
    G per-config updates (which off-TPU used to force the reference backend,
    the PR 1 deviation, and on TPU launched a batched-grid kernel per
    config block).

    Args:
      spec: stacked ClusterSpec, every leaf leading (G,).
      y: (G, L, R, K) decisions; x: (G, L) arrivals; eta: (G,) step sizes.
      operands: optional ``pack_spec_operands_batch(spec)``.
      tiling: optional ``autotune.KernelConfig`` pinning the Pallas tiling
        (default: resolve from the autotune cache on the packed shape).
    Returns y(t+1) (G, L, R, K).
    """
    G, L, R, K = y.shape
    N = R * K
    a_rows, mask_rows, scal_static = (
        pack_spec_operands_batch(spec) if operands is None else operands
    )
    y_rows = jax.vmap(pack_rows)(y).reshape(G * N, L)
    kstar_rows = jax.vmap(_kstar_rows)(spec, y).reshape(G * N, L)
    x_rows = jnp.broadcast_to(
        x.astype(y.dtype)[:, None, :], (G, N, L)
    ).reshape(G * N, L)
    eta_rows = jnp.broadcast_to(
        eta.astype(scal_static.dtype)[:, None], (G, N)
    ).reshape(G * N)
    scal = _og.with_eta(scal_static, eta_rows)
    rows = _dispatch_fused(
        y_rows, a_rows, mask_rows, x_rows, kstar_rows, scal, use_pallas,
        tiling=tiling,
    )
    return jax.vmap(unpack_rows, in_axes=(0, None, None, None))(
        rows.reshape(G, N, L), L, R, K
    )


# ------------------------------------------------------- kernel dispatchers --
def proj_bisect(z, a, mask, c, *, use_pallas: bool | None = None, tiling=None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        if tiling is not None:
            cfg = tiling
        else:
            # cache entries contribute execution layout only — iteration
            # count stays at the kernel default unless pinned explicitly,
            # so cache state can never change values, only speed
            cfg = _at.resolve("proj", *z.shape)._replace(iters=0)
        return _pb.proj_bisect(
            z, a, mask, c, row_block=cfg.row_block,
            iters=cfg.iters or None, interpret=not _on_tpu(),
        )
    return _ref.proj_rows_ref(z, a, mask, c)


def proj_sortscan(z, a, mask, c, *, use_pallas: bool | None = None, tiling=None):
    """Exact in-kernel sortscan projection: Pallas on TPU (interpret mode
    when forced off-TPU), the jnp sortscan sweep otherwise."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        cfg = tiling if tiling is not None else _at.resolve("proj", *z.shape)
        return _ss.proj_sortscan(
            z, a, mask, c, row_block=cfg.row_block, interpret=not _on_tpu()
        )
    return _projection.project_rows_sortscan(z, a, mask, c)


def oga_step_fused(y, a, mask, x, kstar, scal, *,
                   use_pallas: bool | None = None, tiling=None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        if tiling is not None:
            cfg = tiling  # explicit pin: the bisect A/B entry point
        else:
            # cache-resolved configs contribute row_block only; production
            # dispatch always runs the exact sortscan (see _dispatch_fused)
            cfg = _at.resolve("oga_step", *y.shape)._replace(
                method="sortscan", iters=0
            )
        return _og.oga_step_fused(
            y, a, mask, x, kstar, scal, method=cfg.method,
            row_block=cfg.row_block, iters=cfg.iters or None,
            interpret=not _on_tpu(),
        )
    return _ref.oga_step_ref(y, a, mask, x, kstar, scal)


def flash_attention(q, k, v, *, window=None, softcap=None, use_pallas=None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return _fa.flash_attention(
            q, k, v, window=window, softcap=softcap, interpret=not _on_tpu()
        )
    return _ref.flash_attention_ref(q, k, v, window=window, softcap=softcap)
