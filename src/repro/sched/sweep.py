"""Batched scenario-sweep engine (paper §4 evaluation grids).

The trace-driven evaluation sweeps many configurations — learning rate eta0,
decay lambda, utility mix, trace seed, arrival rate rho, contention — and the
old path ran them one at a time through Python (``simulator.run_all`` in a
loop). Here a whole grid becomes ONE jitted/vmapped computation: specs and
arrival tensors are stacked on a leading grid axis on the host, then every
algorithm's scan runs for all configurations simultaneously.

Layers:
  * ``make_grid``         — cartesian product of sweep axes -> list[SweepPoint].
  * ``build_batch``       — trace generation + leaf stacking
                            (trace.make_batch; works only in lifecycle mode;
                            ``trace_backend`` picks host numpy — the
                            bitwise-pinned golden path — or one jitted
                            vmapped device synthesis, sched.trace_device).
  * ``run_algorithm``     — single-config rewards; the one code path shared by
                            ``simulator.run_all`` and the vectorised grid.
  * ``run_grid``          — one jitted dispatch per algorithm over the stacked
                            batch. OGASCHED's fused backend (the default) is
                            grid-flattened: the G axis folds into the fused
                            kernel's row axis (ogasched.run_batch, N = G*R*K
                            rows, one kernel call per step for the grid);
                            heuristics and the reference backend vmap.
  * ``run_grid_sharded``  — the same grid with the G axis laid over a device
                            mesh via shard_map (vmap fallback on one device).
  * ``run_grid_stream`` / ``sweep_stream``
                          — chunked driver: generate, run, and reduce the
                            grid CHUNK_SIZE configs at a time, so 10k-config
                            grids never materialize (G, T, ...) tensors.
                            Chunk prep is double-buffered on a background
                            thread (``iter_batches(prefetch=)``) and large
                            grids synthesize traces on-device by default
                            (``trace_backend="auto"``), so the stream is
                            compute-bound, not trace-bound.
  * ``SweepCheckpoint`` / ``sweep_fingerprint``
                          — crash-safe resume for the streaming driver:
                            completed chunks' reduced summaries are persisted
                            through ckpt.CheckpointManager under a manifest
                            keyed by the grid/chunking/trace-backend
                            fingerprint, so a killed sweep restarted with
                            ``sweep_stream(checkpoint_dir=...)`` verifies it
                            is the SAME sweep, skips finished chunks, and
                            re-enters the prefetch pipeline at the first
                            incomplete chunk.
  * ``summarize`` / ``summarize_lifecycle``
                          — per-config reductions (signed-safe improvement
                            percentages; jitted lifecycle.summarize_batch).

All sweep points must share (L, R, K, T) so stacked leaves are rectangular;
everything else (adjacency, capacities, utility kinds, arrivals, eta0, decay)
may vary per point.

Memory model: a resident ``run_grid`` holds the stacked inputs AND every
algorithm's outputs for all G configs at once — O(G·T) floats in slot mode
but O(G·T·(L + R·K)) in lifecycle mode, which is why large lifecycle grids
must go through the streaming driver (``grid_memory_bytes`` quantifies both).
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import os
import queue as queue_mod
import threading
import time
from functools import lru_cache, partial
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat, obs
from repro.ckpt import checkpoint as ckpt_io
from repro.ckpt.manager import CheckpointManager
from repro.core import baselines, ogasched
from repro.core.graph import ClusterSpec
from repro.kernels import ops
from repro.sched import lifecycle, trace

ALGORITHMS = ("ogasched",) + baselines.BASELINES

MODES = ("slot", "lifecycle")


def _check_mode(mode: str) -> None:
    if mode not in MODES:
        raise ValueError(f"mode must be 'slot' or 'lifecycle', got {mode!r}")


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One grid configuration: a trace plus OGA hyperparameters."""

    cfg: trace.TraceConfig
    eta0: float = 25.0
    decay: float = 0.9999


@dataclasses.dataclass
class SweepBatch:
    """Stacked operands for a grid of G configurations.

    spec leaves, arrivals (and works, lifecycle mode only) carry a leading
    (G,) axis; ``points`` keeps the host-side provenance of each row (same
    order). ``works`` is genuinely optional: slot-mode grids never sample
    job sizes, and ``run_grid(mode="lifecycle")`` rejects a batch without
    them instead of silently running on garbage. ``faults`` is the stacked
    (G, T, K) capacity-multiplier stream, present exactly when some point's
    ``cfg.faults`` is active (lifecycle mode only — fault-free grids carry
    None and compile the pre-fault program unchanged).
    """

    spec: ClusterSpec                   # every leaf (G, ...)
    arrivals: jax.Array                 # (G, T, L)
    eta0: jax.Array                     # (G,)
    decay: jax.Array                    # (G,)
    works: Optional[jax.Array] = None   # (G, T, L) job sizes (lifecycle only)
    faults: Optional[jax.Array] = None  # (G, T, K) capacity multipliers
    points: tuple[SweepPoint, ...] = ()

    @property
    def size(self) -> int:
        return self.arrivals.shape[0]


def make_grid(
    base: Optional[trace.TraceConfig] = None,
    *,
    eta0s: Sequence[float] = (25.0,),
    decays: Sequence[float] = (0.9999,),
    utilities: Sequence[str] = ("mixed",),
    seeds: Optional[Sequence[int]] = None,
    rhos: Optional[Sequence[float]] = None,
    contentions: Optional[Sequence[float]] = None,
) -> list[SweepPoint]:
    """Cartesian product of sweep axes over a base TraceConfig.

    Axis order (slowest to fastest): eta0, decay, utility, seed, rho,
    contention — so neighbouring points share a trace where possible.
    """
    base = trace.TraceConfig() if base is None else base
    seeds = (base.seed,) if seeds is None else seeds
    rhos = (base.rho,) if rhos is None else rhos
    contentions = (base.contention,) if contentions is None else contentions
    points = []
    for eta0, decay, util, seed, rho, cont in itertools.product(
        eta0s, decays, utilities, seeds, rhos, contentions
    ):
        cfg = dataclasses.replace(
            base, utility=util, seed=seed, rho=rho, contention=cont
        )
        points.append(SweepPoint(cfg=cfg, eta0=eta0, decay=decay))
    return points


# "auto" trace backend: grids at or above this many points stream
# device-synthesized traces (sched.trace_device); smaller grids keep the
# bitwise-pinned host path so resident/streamed comparisons stay exact.
DEVICE_TRACE_MIN_POINTS = 1024

TRACE_BACKENDS = ("auto",) + trace.TRACE_BACKENDS


def resolve_trace_backend(trace_backend: str, n_points: int) -> str:
    """"auto" -> "device" for large grids (>= DEVICE_TRACE_MIN_POINTS
    points, where host-side numpy generation would dominate the stream),
    "host" otherwise."""
    if trace_backend not in TRACE_BACKENDS:
        raise ValueError(
            f"trace_backend must be one of {TRACE_BACKENDS}, "
            f"got {trace_backend!r}"
        )
    if trace_backend == "auto":
        return "device" if n_points >= DEVICE_TRACE_MIN_POINTS else "host"
    return trace_backend


def needs_works(algorithms: Sequence[str], mode: str) -> bool:
    """Whether a grid over ``algorithms`` must carry job sizes: always in
    lifecycle mode, and in slot mode exactly when a size-aware baseline
    (baselines.SIZE_AWARE, e.g. "hesrpt") is in the pool. Derived from
    already-fingerprinted fields, so streamed-sweep fingerprints are
    unchanged by the works plumbing."""
    return mode == "lifecycle" or any(
        a in baselines.SIZE_AWARE for a in algorithms
    )


def needs_faults(points: Sequence[SweepPoint], mode: str) -> bool:
    """Whether a grid must carry a fault stream: some point's fault process
    is active. Fault injection is a lifecycle-mode concept (slot mode has
    nothing to evict — allocations are recomputed from full capacity every
    slot), so active fault configs in slot mode fail loudly instead of
    being silently ignored."""
    active = any(p.cfg.faults.active for p in points)
    if active and mode != "lifecycle":
        raise ValueError(
            "fault injection (cfg.faults) requires mode='lifecycle': slot "
            "mode holds nothing across slots, so capacity faults would be "
            "silently ignored"
        )
    return active


def build_batch(
    points: Sequence[SweepPoint],
    mode: str = "slot",
    *,
    trace_backend: str = "host",
    with_works: Optional[bool] = None,
) -> SweepBatch:
    """Generate every point's trace and stack the leaves.

    mode="lifecycle" additionally samples per-job work sizes; slot-mode
    batches carry ``works=None`` unless ``with_works=True`` (size-aware
    slot grids — see ``needs_works``), and fault streams exactly when a
    point's ``cfg.faults`` is active (``needs_faults``). ``trace_backend``
    selects host numpy (bitwise-pinned golden path, the default) or one
    jitted vmapped device generation
    (``trace.make_batch(trace_backend="device")``).
    """
    _check_mode(mode)
    if not points:
        raise ValueError("empty sweep grid")
    if with_works is None:
        with_works = mode == "lifecycle"
    spec, arrivals, works, faults = trace.make_batch(
        [p.cfg for p in points], with_works=with_works,
        trace_backend=resolve_trace_backend(trace_backend, len(points)),
        with_faults=needs_faults(points, mode),
    )
    return SweepBatch(
        spec=spec,
        arrivals=arrivals,
        eta0=jnp.asarray([p.eta0 for p in points], jnp.float32),
        decay=jnp.asarray([p.decay for p in points], jnp.float32),
        works=works,
        faults=faults,
        points=tuple(points),
    )


def run_algorithm(
    spec: ClusterSpec,
    arrivals: jax.Array,
    name: str,
    *,
    eta0: float | jax.Array = 25.0,
    decay: float | jax.Array = 0.9999,
    backend: str = "auto",
    works: Optional[jax.Array] = None,
) -> jax.Array:
    """(T,) per-slot rewards of one algorithm on one configuration.

    This is the single comparison path: ``simulator.run_all`` calls it per
    algorithm, and ``run_grid`` vmaps it over a SweepBatch. Size-aware
    baselines (baselines.SIZE_AWARE) additionally consume ``works`` (T, L)
    job sizes.
    """
    if name == "ogasched":
        rewards, _ = ogasched.run(
            spec, arrivals, eta0=eta0, decay=decay, backend=backend,
        )
        return rewards
    return baselines.run(spec, arrivals, name, works=works)


# --------------------------------------------------------------------------
# vmapped grid bodies — shared by the resident jits and the sharded path, so
# the per-shard computation is the exact computation the one-device grid runs.
# --------------------------------------------------------------------------

def _vmap_slot(spec, arrivals, eta0, decay, *, name, backend, works=None,
               tiling=None):
    if name == "ogasched":
        if ops.resolve_oga_backend(backend) == "fused":
            # grid-flattened: one fused row-kernel call per step covers the
            # whole chunk (N = G*R*K rows) instead of G vmapped scans.
            # ``tiling`` pins the Pallas tile layout — bitwise-pure on the
            # sortscan path, so it stays OUT of sweep_fingerprint with the
            # rest of the execution layout.
            rewards, _ = ogasched.run_batch(
                spec, arrivals, eta0, decay, tiling=tiling
            )
            return rewards
        return jax.vmap(
            lambda s, a, e, d: run_algorithm(
                s, a, name, eta0=e, decay=d, backend=backend,
            )
        )(spec, arrivals, eta0, decay)
    return baselines.run_batch(spec, arrivals, name, works=works)


def _vmap_lifecycle(
    spec, arrivals, works, eta0, decay, rate_floor,
    *, name, backend, queue_depth,
    faults=None, fault_policy=lifecycle.FaultPolicy(),
):
    if faults is None:
        # fault-free grids trace the pre-fault lifecycle program unchanged
        return jax.vmap(
            lambda s, a, w, e, d: lifecycle.run(
                s, a, w, name, eta0=e, decay=d,
                backend=backend, queue_depth=queue_depth,
                rate_floor=rate_floor,
            )
        )(spec, arrivals, works, eta0, decay)
    return jax.vmap(
        lambda s, a, w, e, d, f: lifecycle.run(
            s, a, w, name, eta0=e, decay=d,
            backend=backend, queue_depth=queue_depth, rate_floor=rate_floor,
            faults=f, fault_policy=fault_policy,
        )
    )(spec, arrivals, works, eta0, decay, faults)


def _grid_ogasched(spec, arrivals, eta0, decay, backend, tiling=None):
    return _vmap_slot(
        spec, arrivals, eta0, decay, name="ogasched", backend=backend,
        tiling=tiling,
    )


def _grid_lifecycle(
    spec, arrivals, works, eta0, decay, rate_floor, faults,
    name, backend, queue_depth, fault_policy,
):
    return _vmap_lifecycle(
        spec, arrivals, works, eta0, decay, rate_floor,
        name=name, backend=backend, queue_depth=queue_depth,
        faults=faults, fault_policy=fault_policy,
    )


_run_grid_ogasched = partial(jax.jit, static_argnames=("backend", "tiling"))(
    _grid_ogasched
)
_LIFECYCLE_STATICS = ("name", "backend", "queue_depth", "fault_policy")
_run_grid_lifecycle = partial(jax.jit, static_argnames=_LIFECYCLE_STATICS)(
    _grid_lifecycle
)
# Donated twins for the chunked streaming driver: the chunk's arrival/work
# buffers are handed to XLA for reuse as output storage, capping a streamed
# grid's peak memory at (outputs + inputs - donated) per chunk. Only the
# LAST algorithm of a chunk may donate (earlier dispatches share the
# buffers), and donation is skipped on CPU where XLA cannot use it. The
# fault stream is deliberately NOT donated: it is tiny (T*K vs T*L rows)
# and None for fault-free grids, where a donate_argnums entry pointing at
# an empty pytree would be a silent no-op trap.
_run_grid_ogasched_donated = partial(
    jax.jit, static_argnames=("backend", "tiling"), donate_argnums=(1,)
)(_grid_ogasched)
_run_grid_lifecycle_donated = partial(
    jax.jit, static_argnames=_LIFECYCLE_STATICS, donate_argnums=(1, 2)
)(_grid_lifecycle)


def _algorithm_backend(name: str, backend: str) -> str:
    """``backend`` selects the OGA update only; heuristics have no kernel."""
    return backend if name == "ogasched" else "reference"


def _donation_applies(algorithms: Sequence[str], mode: str) -> bool:
    """Whether ``run_grid(donate=True)`` can actually donate: every
    lifecycle dispatch has a donated twin, but in slot mode only the
    OGASCHED dispatch does (baselines.run_batch takes no donation)."""
    if mode == "lifecycle":
        return len(algorithms) > 0
    return "ogasched" in algorithms


def run_grid(
    batch: SweepBatch,
    algorithms: Sequence[str] = ALGORITHMS,
    *,
    backend: str = "auto",
    mode: str = "slot",
    queue_depth: int = 8,
    rate_floor: float = 1e-3,
    donate: bool = False,
    fault_policy: lifecycle.FaultPolicy = lifecycle.FaultPolicy(),
    tiling=None,
) -> dict[str, jax.Array] | dict[str, lifecycle.LifecycleTrace]:
    """Run every algorithm over every configuration.

    mode="slot" (default): {name: (G, T) rewards}, allocations recomputed
    from full capacity each slot. mode="lifecycle": jobs hold resources
    until their work drains (sched.lifecycle); returns {name:
    LifecycleTrace} with every leaf leading (G, T, ...) — reduce with
    ``summarize_lifecycle``.

    ``backend`` applies to OGASCHED only and defaults to "auto" == "fused"
    everywhere: in slot mode the grid axis is flattened into the fused
    kernel's row axis (ogasched.run_batch — one kernel call per step for
    the whole grid), off-TPU the packed rows run through the pure-jnp path
    with the exact sorted projection. "reference" keeps the vmapped
    three-pass update for A/B.

    ``donate=True`` hands ``batch.arrivals`` (and ``works``) to XLA on the
    final donation-capable dispatch so their buffers can back the outputs —
    the streaming driver uses it per chunk. In slot mode only the OGASCHED
    dispatch can donate, so it is reordered to run last; the returned dict
    always follows ``algorithms`` order. The donated leaves are dead
    afterwards; callers must not reuse the batch. No-op on CPU or when no
    dispatch can donate.

    ``batch.faults`` (built by ``build_batch`` when a point's fault process
    is active) runs every lifecycle row against its surviving capacity;
    ``fault_policy`` sets the eviction/retry/backoff knobs (static — one
    compile per policy).

    ``tiling`` (a ``kernels.autotune.KernelConfig``) pins the fused-kernel
    Pallas tiling for the OGASCHED slot dispatch; default resolves from
    the autotune cache. Execution layout only — never fingerprinted.
    """
    _check_mode(mode)
    if batch.works is None and needs_works(algorithms, mode):
        raise ValueError(
            "grid needs job sizes: build_batch(points, mode='lifecycle') "
            "or build_batch(points, with_works=True) for size-aware "
            "slot-mode baselines"
        )
    donate = (
        donate and jax.default_backend() != "cpu"
        and _donation_applies(algorithms, mode)
    )
    order = list(algorithms)
    if donate and mode != "lifecycle":
        # only the OGASCHED dispatch has a donated twin in slot mode: run it
        # last, once no other algorithm needs the arrival buffer (stable
        # sort — baseline order is preserved)
        order.sort(key=lambda n: n == "ogasched")
    out: dict = {}
    for i, name in enumerate(order):
        last = donate and i == len(order) - 1
        if mode == "lifecycle":
            fn = _run_grid_lifecycle_donated if last else _run_grid_lifecycle
            out[name] = fn(
                batch.spec, batch.arrivals, batch.works, batch.eta0,
                batch.decay, jnp.asarray(rate_floor, jnp.float32),
                batch.faults,
                name, _algorithm_backend(name, backend), queue_depth,
                fault_policy,
            )
        elif name == "ogasched":
            fn = _run_grid_ogasched_donated if last else _run_grid_ogasched
            out[name] = fn(
                batch.spec, batch.arrivals, batch.eta0, batch.decay, backend,
                tiling,
            )
        else:
            out[name] = baselines.run_batch(
                batch.spec, batch.arrivals, name,
                works=batch.works if name in baselines.SIZE_AWARE else None,
            )
    return {name: out[name] for name in algorithms}


# --------------------------------------------------------------------------
# Sharded grids: the G axis laid over a 1-D device mesh via shard_map. Each
# device runs the plain vmapped grid on its G/n block — rows are independent,
# so the program has no collectives and results match run_grid bitwise.
# --------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _sharded_grid_fn(
    mesh: Mesh, name: str, mode: str, backend: str, queue_depth: int,
    fault_policy: lifecycle.FaultPolicy = lifecycle.FaultPolicy(),
    has_faults: bool = False,
    tiling=None,
):
    gspec = P(mesh.axis_names[0])
    if mode == "lifecycle" and has_faults:
        def body(spec, arrivals, works, eta0, decay, rate_floor, faults):
            return _vmap_lifecycle(
                spec, arrivals, works, eta0, decay, rate_floor,
                name=name, backend=backend, queue_depth=queue_depth,
                faults=faults, fault_policy=fault_policy,
            )
        in_specs = (gspec, gspec, gspec, gspec, gspec, P(), gspec)
    elif mode == "lifecycle":
        def body(spec, arrivals, works, eta0, decay, rate_floor):
            return _vmap_lifecycle(
                spec, arrivals, works, eta0, decay, rate_floor,
                name=name, backend=backend, queue_depth=queue_depth,
            )
        in_specs = (gspec, gspec, gspec, gspec, gspec, P())
    elif name in baselines.SIZE_AWARE:
        def body(spec, arrivals, works, eta0, decay):
            return _vmap_slot(
                spec, arrivals, eta0, decay,
                name=name, backend=backend, works=works,
            )
        in_specs = (gspec, gspec, gspec, gspec, gspec)
    else:
        def body(spec, arrivals, eta0, decay):
            return _vmap_slot(
                spec, arrivals, eta0, decay, name=name, backend=backend,
                tiling=tiling,
            )
        in_specs = (gspec, gspec, gspec, gspec)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=gspec, check_vma=False,
    ))


def _pad_rows(tree, pad: int):
    """Repeat the last grid row ``pad`` times on every leaf."""
    if pad == 0:
        return tree
    return jax.tree.map(
        lambda l: jnp.concatenate([l, jnp.repeat(l[-1:], pad, axis=0)]), tree
    )


def run_grid_sharded(
    batch: SweepBatch,
    algorithms: Sequence[str] = ALGORITHMS,
    *,
    mesh: Optional[Mesh] = None,
    backend: str = "auto",
    mode: str = "slot",
    queue_depth: int = 8,
    rate_floor: float = 1e-3,
    fault_policy: lifecycle.FaultPolicy = lifecycle.FaultPolicy(),
    tiling=None,
) -> dict[str, jax.Array] | dict[str, lifecycle.LifecycleTrace]:
    """``run_grid`` with the grid axis sharded over a device mesh.

    ``mesh`` must be 1-D (any axis name); default is a mesh over all local
    devices (compat.grid_mesh). On a single-device host this falls back
    transparently to the resident vmap path, so callers can use it
    unconditionally. Grids that do not divide the device count are padded
    by repeating the last row, and the padding is sliced off the outputs.
    """
    _check_mode(mode)
    if mesh is None:
        mesh = compat.grid_mesh()
    if mesh is None or mesh.size <= 1:
        return run_grid(
            batch, algorithms, backend=backend, mode=mode,
            queue_depth=queue_depth, rate_floor=rate_floor,
            fault_policy=fault_policy, tiling=tiling,
        )
    if batch.works is None and needs_works(algorithms, mode):
        raise ValueError(
            "grid needs job sizes: build_batch(points, mode='lifecycle') "
            "or build_batch(points, with_works=True) for size-aware "
            "slot-mode baselines"
        )
    G = batch.size
    pad = (-G) % mesh.size
    spec = _pad_rows(batch.spec, pad)
    arrivals = _pad_rows(batch.arrivals, pad)
    eta0 = _pad_rows(batch.eta0, pad)
    decay = _pad_rows(batch.decay, pad)
    out: dict = {}
    for name in algorithms:
        fn = _sharded_grid_fn(
            mesh, name, mode, _algorithm_backend(name, backend), queue_depth,
            fault_policy, batch.faults is not None, tiling,
        )
        if mode == "lifecycle" and batch.faults is not None:
            res = fn(
                spec, arrivals, _pad_rows(batch.works, pad), eta0, decay,
                jnp.asarray(rate_floor, jnp.float32),
                _pad_rows(batch.faults, pad),
            )
        elif mode == "lifecycle":
            res = fn(
                spec, arrivals, _pad_rows(batch.works, pad), eta0, decay,
                jnp.asarray(rate_floor, jnp.float32),
            )
        elif name in baselines.SIZE_AWARE:
            res = fn(spec, arrivals, _pad_rows(batch.works, pad), eta0, decay)
        else:
            res = fn(spec, arrivals, eta0, decay)
        out[name] = jax.tree.map(lambda l: l[:G], res) if pad else res
    return out


# --------------------------------------------------------------------------
# Resumable sweeps: per-chunk summary checkpoints + a fingerprinted manifest.
# The chunk is the unit of progress — each completed chunk's reduced outputs
# are committed through the crash-hardened ckpt layer, so a SIGKILLed sweep
# restarts from its first incomplete chunk instead of from zero.
# --------------------------------------------------------------------------

class SweepResumeMismatch(ValueError):
    """A checkpoint directory belongs to a *different* sweep: its manifest
    fingerprint does not match the (grid, chunking, trace-backend, run
    parameters) being resumed. Resuming would silently splice summaries of
    unrelated configurations — refuse instead."""


def sweep_fingerprint(
    points: Sequence[SweepPoint],
    algorithms: Sequence[str] = ALGORITHMS,
    *,
    chunk_size: int,
    mode: str = "slot",
    trace_backend: str = "auto",
    backend: str = "auto",
    queue_depth: int = 8,
    rate_floor: float = 1e-3,
    fault_policy: lifecycle.FaultPolicy = lifecycle.FaultPolicy(),
) -> str:
    """SHA-256 over everything that determines a streamed sweep's summaries.

    Covers every point's full TraceConfig + hyperparameters (order matters:
    chunk index -> grid rows; ``cfg.faults`` recurses into the row dict, so
    the fault process is fingerprinted per point), the algorithm list,
    chunking, mode, the RESOLVED trace backend (so ``"auto"`` and the
    concrete backend it resolves to fingerprint identically), and the run
    parameters that reach the kernels — including the eviction/retry
    ``fault_policy``. Execution layout — ``sharded``, ``prefetch``,
    ``donate`` — is deliberately excluded: those are bitwise-pure
    reorganisations (pinned by tests/test_sweep_sharded.py,
    test_sweep_stream.py), so a sweep checkpointed on one host may resume
    on a different device count.
    """
    h = hashlib.sha256()
    header = {
        "algorithms": list(algorithms),
        "chunk_size": int(chunk_size),
        "mode": mode,
        "trace_backend": resolve_trace_backend(trace_backend, len(points)),
        "backend": backend,
        "queue_depth": int(queue_depth),
        "rate_floor": float(rate_floor),
        "fault_policy": dataclasses.asdict(fault_policy),
        "n_points": len(points),
    }
    h.update(json.dumps(header, sort_keys=True).encode())
    for p in points:
        row = dataclasses.asdict(p.cfg)
        row["eta0"] = float(p.eta0)
        row["decay"] = float(p.decay)
        h.update(json.dumps(row, sort_keys=True, default=float).encode())
    return h.hexdigest()


class SweepCheckpoint:
    """Crash-safe store for a streamed sweep's per-chunk summaries.

    Layout: ``<dir>/sweep_manifest.json`` binds the directory to ONE sweep
    (its ``sweep_fingerprint`` plus human-readable provenance), published
    atomically; chunk ``i``'s reduced summary is checkpoint step ``i``
    through :class:`repro.ckpt.manager.CheckpointManager` (``keep=None`` —
    every chunk is retained; manager init sweeps ``.tmp.*`` orphans from a
    killed writer). Summary dicts are stored as arrays sorted by metric
    name, with the names in the step manifest (``metrics``), so restore
    needs no live pytree.

    Progress is the **contiguous valid prefix** of chunk checkpoints: the
    driver commits chunks in order, so the first missing-or-torn step is
    exactly where a killed sweep re-enters the prefetch pipeline. A torn
    final write (SIGKILL mid-commit) therefore costs one chunk, never the
    sweep.
    """

    MANIFEST = "sweep_manifest.json"

    def __init__(
        self,
        directory: str,
        points: Sequence[SweepPoint],
        algorithms: Sequence[str] = ALGORITHMS,
        *,
        chunk_size: int = 64,
        mode: str = "slot",
        trace_backend: str = "auto",
        backend: str = "auto",
        queue_depth: int = 8,
        rate_floor: float = 1e-3,
        fault_policy: lifecycle.FaultPolicy = lifecycle.FaultPolicy(),
    ):
        self.dir = directory
        self.chunk_size = int(chunk_size)
        self.num_chunks = -(-len(points) // self.chunk_size)
        self.fingerprint = sweep_fingerprint(
            points, algorithms, chunk_size=chunk_size, mode=mode,
            trace_backend=trace_backend, backend=backend,
            queue_depth=queue_depth, rate_floor=rate_floor,
            fault_policy=fault_policy,
        )
        self.manager = CheckpointManager(directory, keep=None, every=1)
        man_path = os.path.join(directory, self.MANIFEST)
        if os.path.exists(man_path):
            with open(man_path) as f:
                have = json.load(f)
            if have.get("fingerprint") != self.fingerprint:
                raise SweepResumeMismatch(
                    f"checkpoint directory {directory!r} belongs to a "
                    "different sweep (grid/chunking/trace-backend/run-"
                    "parameter fingerprint mismatch); point it at a fresh "
                    "directory or rebuild the same grid"
                )
        else:
            manifest = {
                "fingerprint": self.fingerprint,
                "n_points": len(points),
                "chunk_size": self.chunk_size,
                "num_chunks": self.num_chunks,
                "mode": mode,
                "algorithms": list(algorithms),
            }
            tmp = man_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, man_path)

    def completed_chunks(self) -> int:
        """Chunks durably finished: the contiguous valid prefix length."""
        n = 0
        while n < self.num_chunks and ckpt_io.verify_checkpoint(self.dir, n):
            n += 1
        return n

    def commit(self, chunk_index: int, summary: dict) -> None:
        """Durably record chunk ``chunk_index``'s reduced summary."""
        keys = sorted(summary)
        self.manager.save(
            chunk_index,
            [np.asarray(summary[k]) for k in keys],
            extra={"metrics": keys},
        )

    def load_summaries(self) -> list[dict[str, np.ndarray]]:
        """Finished chunks' summaries, in chunk order (the valid prefix)."""
        out = []
        for i in range(self.completed_chunks()):
            man = ckpt_io.read_manifest(self.dir, i)
            arrays = ckpt_io.load_checkpoint_arrays(self.dir, i)
            out.append(dict(zip(man["metrics"], arrays)))
        return out


# --------------------------------------------------------------------------
# Streaming grids: generate -> run -> reduce, one chunk at a time. A chunk is
# the only resident (g, T, ...) tensor set; 10k-config grids stream through
# in O(chunk_size) memory. The last partial chunk is padded to chunk_size so
# every chunk reuses one compiled program, then trimmed before it is yielded.
# --------------------------------------------------------------------------

def _chunk_batches(
    points: Sequence[SweepPoint],
    chunk_size: int,
    mode: str,
    trace_backend: str,
    start_chunk: int = 0,
    with_works: Optional[bool] = None,
) -> Iterator[tuple[slice, SweepBatch]]:
    """Synchronous chunk generation — the prefetch worker's body."""
    for start in range(start_chunk * chunk_size, len(points), chunk_size):
        chunk = list(points[start:start + chunk_size])
        with obs.span("sweep.synthesis"):
            batch = build_batch(
                chunk, mode=mode, trace_backend=trace_backend,
                with_works=with_works,
            )
        pad = chunk_size - len(chunk)
        if pad:
            batch = SweepBatch(
                spec=_pad_rows(batch.spec, pad),
                arrivals=_pad_rows(batch.arrivals, pad),
                eta0=_pad_rows(batch.eta0, pad),
                decay=_pad_rows(batch.decay, pad),
                works=None if batch.works is None
                else _pad_rows(batch.works, pad),
                faults=None if batch.faults is None
                else _pad_rows(batch.faults, pad),
                points=batch.points,
            )
        yield slice(start, start + len(chunk)), batch


class _PrefetchFailed:
    """Worker-thread exception carrier (re-raised on the consumer side)."""

    def __init__(self, exc: BaseException):
        self.exc = exc


_DONE = object()


def _prefetched(it: Iterator, depth: int) -> Iterator:
    """Drive ``it`` on a background thread through a bounded queue.

    The producer stays exactly ``depth`` items ahead of the consumer —
    double-buffering at the default depth 2 — so host-side chunk prep
    (trace generation, padding, device upload) overlaps the device compute
    the consumer dispatches. Order is preserved, exceptions propagate, and
    abandoning the iterator (``close``/GeneratorExit) stops the worker.
    """
    q: queue_mod.Queue = queue_mod.Queue(maxsize=depth)
    stop = threading.Event()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue_mod.Full:
                continue
        return False

    def worker():
        try:
            for item in it:
                if not _put(item):
                    return
            _put(_DONE)
        except BaseException as exc:  # re-raised by the consumer
            _put(_PrefetchFailed(exc))

    t = threading.Thread(
        target=worker, name="sweep-chunk-prefetch", daemon=True
    )
    t.start()
    try:
        while True:
            item = q.get()
            if item is _DONE:
                return
            if isinstance(item, _PrefetchFailed):
                raise item.exc
            yield item
    finally:
        stop.set()
        # Wait (bounded) for the worker to notice: a daemon thread killed
        # mid-XLA-dispatch at interpreter teardown aborts the process. The
        # worker re-checks ``stop`` every 0.1 s when queue-blocked, so the
        # only wait is the chunk generation already in flight.
        t.join(timeout=30.0)


def iter_batches(
    points: Sequence[SweepPoint],
    chunk_size: int,
    *,
    mode: str = "slot",
    trace_backend: str = "host",
    prefetch: int = 2,
    start_chunk: int = 0,
    with_works: Optional[bool] = None,
) -> Iterator[tuple[slice, SweepBatch]]:
    """Yield ``(grid_slice, batch)`` chunks of a point list.

    Each batch carries exactly ``chunk_size`` rows: a final partial chunk is
    padded by repeating its already-generated last row (``_pad_rows``, no
    extra trace generation), while ``points`` keeps only the real points.
    ``grid_slice`` is the un-padded range of the full grid the chunk covers,
    so ``batch.arrivals[: sl.stop - sl.start]`` are the real rows.

    ``prefetch`` > 0 generates chunks on a background thread through a
    bounded queue of that depth (default 2: double buffering), so the next
    chunk's trace synthesis and upload overlap the caller's device compute
    instead of serializing with it. ``prefetch=0`` keeps the old fully
    synchronous behaviour. Chunk order and contents are identical either
    way. ``trace_backend`` is resolved against the FULL grid size (not the
    chunk), so "auto" picks the device path exactly when the grid is large
    enough for generation cost to matter.

    ``start_chunk`` skips that many leading chunks entirely — no trace is
    generated for them and the prefetch pipeline fills starting at the
    first emitted chunk. This is how a resumed sweep re-enters the stream
    at its first incomplete chunk.
    """
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    if start_chunk < 0:
        raise ValueError(f"start_chunk must be >= 0, got {start_chunk}")
    backend = resolve_trace_backend(trace_backend, len(points))
    it = _chunk_batches(
        points, chunk_size, mode, backend, start_chunk, with_works,
    )
    if prefetch > 0:
        it = _prefetched(it, prefetch)
    yield from it


def run_grid_stream(
    points: Sequence[SweepPoint],
    algorithms: Sequence[str] = ALGORITHMS,
    *,
    chunk_size: int = 64,
    mode: str = "slot",
    sharded: bool = False,
    backend: str = "auto",
    trace_backend: str = "auto",
    prefetch: int = 2,
    queue_depth: int = 8,
    rate_floor: float = 1e-3,
    donate: bool = False,
    stats: Optional[dict] = None,
    checkpoint: Optional[SweepCheckpoint] = None,
    fault_policy: lifecycle.FaultPolicy = lifecycle.FaultPolicy(),
    tiling=None,
) -> Iterator[tuple[slice, SweepBatch, dict]]:
    """Stream a grid chunk by chunk: yields ``(grid_slice, batch, outputs)``.

    Traces are generated, run, and handed back per chunk — at no point does
    a (G, T, ...) tensor for the full grid exist on host or device. Both
    the yielded batch and outputs are trimmed to the chunk's true size.
    ``sharded=True`` routes each chunk through ``run_grid_sharded`` (chunks
    then shard over the device mesh; keep chunk_size a multiple of the
    device count to avoid padding).

    Chunk generation is double-buffered: ``iter_batches`` prepares the next
    ``prefetch`` chunks on a background thread while this thread's chunk
    computes, so the stream is compute-bound, not trace-bound.
    ``trace_backend="auto"`` additionally synthesizes the traces of large
    grids (>= DEVICE_TRACE_MIN_POINTS points) on-device
    (``sched.trace_device``); smaller grids keep the bitwise-pinned host
    path, so streamed == resident comparisons stay exact by default.

    ``donate=True`` donates each chunk's arrival/work buffers to the final
    algorithm's dispatch (run_grid's donation) to cap peak device memory;
    the yielded batch then carries ``arrivals=None`` / ``works=None``.
    Ignored on CPU and under ``sharded=True``. Donation composes with
    prefetching because every queued chunk is a distinct buffer set the
    worker built independently — donating the current chunk can never
    alias a chunk still in (or entering) the queue.

    Pass a dict as ``stats`` to receive pipeline telemetry: the driver
    accumulates ``chunk_wait_s``, the time this thread stalled waiting on
    the prefetched chunk pipeline (trace synthesis + padding + upload that
    the background worker failed to hide). Benchmarks derive their
    ``overlap_ratio`` from it against the production driver itself rather
    than a re-implementation.

    ``checkpoint`` (a :class:`SweepCheckpoint` built for THIS grid and
    these run parameters — fingerprints are compared, mismatch raises
    :class:`SweepResumeMismatch`) makes the stream resumable: chunks the
    store already holds are skipped — never generated, never yielded —
    and the prefetch pipeline fills from the first incomplete chunk. The
    driver does not commit: the caller owns the reduction, so after
    consuming a yielded chunk it calls
    ``checkpoint.commit(sl.start // chunk_size, reduced)`` with whatever
    it accumulates (``sweep_stream`` does exactly this with its summary
    dicts). Composes with ``sharded``, ``donate``, and ``prefetch``.
    """
    needs_faults(points, mode)  # slot-mode fault configs fail before chunk 0
    start_chunk = 0
    if checkpoint is not None:
        fp = sweep_fingerprint(
            points, algorithms, chunk_size=chunk_size, mode=mode,
            trace_backend=trace_backend, backend=backend,
            queue_depth=queue_depth, rate_floor=rate_floor,
            fault_policy=fault_policy,
        )
        if fp != checkpoint.fingerprint:
            raise SweepResumeMismatch(
                "run_grid_stream arguments do not match the sweep this "
                "checkpoint store was built for"
            )
        start_chunk = checkpoint.completed_chunks()
    donate = (
        donate and not sharded and jax.default_backend() != "cpu"
        and _donation_applies(algorithms, mode)
    )
    runner = run_grid_sharded if sharded else run_grid
    kw = {"donate": True} if donate else {}
    kw["fault_policy"] = fault_policy
    kw["tiling"] = tiling  # execution layout, like donate — not fingerprinted
    it = iter_batches(
        points, chunk_size, mode=mode,
        trace_backend=trace_backend, prefetch=prefetch,
        start_chunk=start_chunk,
        with_works=needs_works(algorithms, mode),
    )
    while True:
        t_wait = time.monotonic()
        with obs.span("sweep.wait"):
            item = next(it, None)
        if stats is not None:
            stats["chunk_wait_s"] = (
                stats.get("chunk_wait_s", 0.0) + time.monotonic() - t_wait
            )
        if item is None:
            return
        sl, batch = item
        with obs.span("sweep.dispatch"):
            out = runner(
                batch, algorithms, backend=backend, mode=mode,
                queue_depth=queue_depth, rate_floor=rate_floor, **kw,
            )
        g = sl.stop - sl.start
        trim = g < batch.size
        if trim:
            out = {n: jax.tree.map(lambda l: l[:g], v) for n, v in out.items()}
        if trim or donate:
            batch = SweepBatch(
                spec=jax.tree.map(lambda l: l[:g], batch.spec),
                arrivals=None if donate else batch.arrivals[:g],
                eta0=batch.eta0[:g],
                decay=batch.decay[:g],
                works=None if donate or batch.works is None
                else batch.works[:g],
                faults=None if batch.faults is None else batch.faults[:g],
                points=batch.points,
            )
        yield sl, batch, out


def sweep_stream(
    points: Sequence[SweepPoint],
    algorithms: Sequence[str] = ALGORITHMS,
    *,
    chunk_size: int = 64,
    mode: str = "slot",
    sharded: bool = False,
    backend: str = "auto",
    trace_backend: str = "auto",
    prefetch: int = 2,
    queue_depth: int = 8,
    rate_floor: float = 1e-3,
    checkpoint_dir: Optional[str] = None,
    fault_policy: lifecycle.FaultPolicy = lifecycle.FaultPolicy(),
    tiling=None,
) -> dict[str, np.ndarray]:
    """Full-grid per-config summaries via the streaming driver.

    Returns exactly what ``summarize`` (slot mode) / ``summarize_lifecycle``
    (lifecycle mode) return for a resident ``run_grid`` of the same points —
    {metric/name: (G,)} — but with peak memory bounded by ``chunk_size``
    configs. Reduction happens per chunk (chunk input buffers donated to
    the final dispatch off-CPU); only the (G,)-sized summary rows
    accumulate. Chunk generation is prefetched on a background thread
    (``prefetch``, default double-buffered) and ``trace_backend="auto"``
    moves trace synthesis on-device for large grids — see
    ``run_grid_stream``.

    ``checkpoint_dir`` makes the sweep **preemption-tolerant**: every
    completed chunk's summary is committed to a :class:`SweepCheckpoint`
    store there (cadence = one commit per chunk — the summaries are
    (chunk_size,)-sized rows, so commits cost microseconds against chunk
    compute), and a rerun with the same arguments loads the finished
    prefix from disk and computes only the remaining chunks. The store is
    fingerprint-bound: pointing it at a different grid/chunking/run
    raises :class:`SweepResumeMismatch`. Resumed summaries are
    bitwise-identical to an uninterrupted run (the store round-trips the
    float arrays exactly; tests/test_sweep_resume.py SIGKILLs a live
    sweep to prove it).
    """
    ckpt = None
    parts: dict[str, list[np.ndarray]] = {}
    if checkpoint_dir is not None:
        ckpt = SweepCheckpoint(
            checkpoint_dir, points, algorithms, chunk_size=chunk_size,
            mode=mode, trace_backend=trace_backend, backend=backend,
            queue_depth=queue_depth, rate_floor=rate_floor,
            fault_policy=fault_policy,
        )
        for summ in ckpt.load_summaries():
            for k, v in summ.items():
                parts.setdefault(k, []).append(v)
    for sl, batch, out in run_grid_stream(
        points, algorithms, chunk_size=chunk_size, mode=mode,
        sharded=sharded, backend=backend, trace_backend=trace_backend,
        prefetch=prefetch,
        queue_depth=queue_depth, rate_floor=rate_floor, donate=True,
        checkpoint=ckpt, fault_policy=fault_policy, tiling=tiling,
    ):
        summ = (
            summarize_lifecycle(out, batch) if mode == "lifecycle"
            else summarize(out)
        )
        summ = {k: np.asarray(v) for k, v in summ.items()}
        if ckpt is not None:
            ckpt.commit(sl.start // chunk_size, summ)
        for k, v in summ.items():
            parts.setdefault(k, []).append(v)
    return {k: np.concatenate(v) for k, v in parts.items()}


def grid_memory_bytes(
    cfg: trace.TraceConfig,
    G: int,
    *,
    mode: str = "slot",
    algorithms: Sequence[str] = ALGORITHMS,
    itemsize: int = 4,
    prefetch: int = 0,
) -> dict[str, int]:
    """Analytic resident-memory estimate for a G-config grid.

    {"inputs": stacked spec/arrival/work bytes, "outputs": every algorithm's
    result tensors, "prefetch_buffers": staged not-yet-consumed chunks,
    "total": all of it}. The streaming driver's peak is the same formula
    evaluated at G=chunk_size with ``prefetch`` set to its queue depth
    (default 2): on top of the in-flight chunk the pipeline holds up to
    ``prefetch`` queued chunks' *inputs* (their outputs don't exist yet)
    PLUS one more the worker is building while the queue is full —
    ``prefetch + 1`` staged chunks total — plus O(G) summary rows.
    Lifecycle outputs dominate either way: a LifecycleTrace row costs
    T·(4 + 8L + R·K) floats vs slot mode's T (the fault-robustness leaves
    — evicted, wasted, rdropped, work_done — are carried whether or not a
    fault stream runs; the (T, K) fault input only when ``cfg.faults`` is
    active).
    """
    _check_mode(mode)
    L, R, K, T = cfg.L, cfg.R, cfg.K, cfg.T
    spec = L * R + L * K + 2 * R * K + 2 * K
    inputs = spec + T * L + 2  # + arrivals + (eta0, decay)
    per_alg = T  # slot-mode rewards
    if mode == "lifecycle":
        inputs += T * L  # works
        if cfg.faults.active:
            inputs += T * K  # fault capacity multipliers
        per_alg = T * (4 + 8 * L + R * K)  # LifecycleTrace leaves
    in_b = G * inputs * itemsize
    out_b = G * per_alg * len(algorithms) * itemsize
    pre_b = (prefetch + 1) * in_b if prefetch else 0
    return {
        "inputs": in_b,
        "outputs": out_b,
        "prefetch_buffers": pre_b,
        "total": in_b + out_b + pre_b,
    }


# --------------------------------------------------------------------------
# Reductions
# --------------------------------------------------------------------------

def improvement_pct(oga, base, eps: float = 1e-9):
    """Signed-safe percentage improvement of ``oga`` over ``base``.

    The naive ``100*(oga/base - 1)`` emits inf/NaN when a baseline's average
    reward is 0 and flips sign when it is negative — and rewards are gain
    *minus* communication penalty, so negative baseline averages are
    reachable at high contention. This uses
    ``100 * (oga - base) / max(|base|, eps)``: identical to the naive form
    for positive baselines, finite everywhere, and its sign always matches
    ``sign(oga - base)``.
    """
    oga = np.asarray(oga, np.float64)
    base = np.asarray(base, np.float64)
    return 100.0 * (oga - base) / np.maximum(np.abs(base), eps)


def summarize(rewards: dict[str, jax.Array]) -> dict[str, np.ndarray]:
    """Per-config average rewards + OGASCHED improvement percentages.

    Returns {"avg/<name>": (G,), "improvement_pct/<name>": (G,)} mirroring
    ``simulator.improvement_over_baselines`` per grid row.
    """
    with obs.span("sweep.summarize"):
        out = {f"avg/{n}": np.asarray(r).mean(axis=1)
               for n, r in rewards.items()}
        if "ogasched" in rewards:
            oga = out["avg/ogasched"]
            for n in rewards:
                if n != "ogasched":
                    out[f"improvement_pct/{n}"] = improvement_pct(
                        oga, out[f"avg/{n}"]
                    )
    return out


def summarize_lifecycle(
    traces: dict[str, lifecycle.LifecycleTrace], batch: SweepBatch
) -> dict[str, np.ndarray]:
    """Per-config lifecycle metrics: {"<metric>/<name>": (G,)} for every
    scalar ``lifecycle.summarize`` reports (jct_mean, jct_p99,
    slowdown_mean, utilization, ...). One jitted reduction per algorithm
    (lifecycle.summarize_batch) — no per-row Python loop."""
    out: dict[str, np.ndarray] = {}
    with obs.span("sweep.summarize"):
        for name, tr in traces.items():
            for metric, v in lifecycle.summarize_batch(
                tr, batch.spec
            ).items():
                out[f"{metric}/{name}"] = np.asarray(v)
    return out
