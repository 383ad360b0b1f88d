"""Shape-aware autotuning for the scheduler kernels.

This module is the single home of every tiling constant in the kernel
package (the ``hardcoded-tiling`` lint rule enforces that), the enumerator
of legal tiling configurations per packed problem shape, the measurement
harness that benchmarks candidates with compile-time excluded, and the
persistent on-disk winner cache that ``kernels.ops`` dispatch resolves
tilings from.

Design contract, in dispatch order:

* ``resolve(kernel, n, l)`` is the ONLY entry the hot path calls. It is
  pure Python over static shapes (safe at jit trace time), consults the
  in-memory view of the on-disk table under ``$REPRO_AUTOTUNE_CACHE``, and
  on a miss, or when that variable is unset (then no file is read),
  answers with ``shape_rule(n, l)``: the widest row block the packed
  shape allows. It NEVER measures —
  ``tests/test_autotune.py`` pins the warmed sweep path at zero
  measurements, and the CI ``kernel-gate`` fails on cache misses in the
  warmed bench path.
* ``tune(kernel, n, l)`` enumerates ``candidates()``, benchmarks each with
  warmup + ``compat.CompilationCounter`` compile-exclusion, and publishes
  the winner into the on-disk table through the hardened ckpt write path
  (``ckpt.atomic_write_json`` — temp file, fsync, atomic rename, directory
  fsync), so a crash mid-store can never tear the table.
* Cache keys bucket shapes (rows to the next power of two, lanes to the
  next ``LANE_FLOOR`` multiple — the padded shapes the kernels actually
  run) and bind the backend platform and jax version, so a cache written
  on one machine/toolchain is a clean miss, not a wrong answer, on
  another. Corrupt or stale entries are validated on read and treated as
  misses, never crashes (same torn-write discipline as tests/test_ckpt).
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, NamedTuple, Optional, Sequence

import jax

from repro import ckpt

# --------------------------------------------------------------------------
# Tiling constants — the one place integer tile shapes may be spelled out
# (lint rule ``hardcoded-tiling``; everything else references these names).
# --------------------------------------------------------------------------

LANE_FLOOR = 128          # TPU vector lane width: last dim pads to this
SUBLANE_FLOOR = 8         # f32 sublane granularity: row blocks are multiples
# legal row-block candidates; on a v5e no shape ran fastest at 512 (PERF.md)
ROW_BLOCKS = (8, 16, 32, 64, 128, 256)
BISECT_ITERS = (12, 20, 28)         # bisect-fallback iteration candidates
DEFAULT_BISECT_ITERS = 20
PROJ_METHODS = ("sortscan", "bisect")
DEFAULT_PROJ_METHOD = "sortscan"    # exact in-kernel breakpoint sweep
SCAL_LANES = LANE_FLOOR   # packed-scalar operand rides one lane block
# flash-attention tile shapes (MXU-aligned); kernels.flash_attention reads
# these rather than spelling its own
FLASH_BLOCK_Q = 128
FLASH_BLOCK_K = 128
# the v5e's default scoped VMEM limit, as its compiler reports it ("limit
# 16.00M"); a tile whose sortscan footprint (``vmem_bytes``) exceeds it is
# neither enumerated nor chosen by the shape rule
VMEM_BUDGET = 16 * 1024 * 1024
# the fused sortscan step's scoped VMEM per row and sort lane, and per
# squared sort lane: the v5e compiler's own accounting bounded by these at
# every measured (row block, lanes) point, 8-512 rows x 128-1536 lanes
VMEM_BYTES_PER_ROW_LANE = 48
VMEM_LANE_SQ_DIVISOR = 8
# row block x sort lanes at which the fused sortscan step ran fastest on a
# v5e: 64 vregs per sort temporary. Every shape swept won there (256 rows
# at 128 lanes, 128 at 256) and lost at twice it (PERF.md)
SORT_TILE_MAX = 64 * 1024
# fewest grid steps at which a wider tile still won on the v5e (3,072 rows
# at 256); fewer were not measured
MIN_GRID_STEPS = 12

TABLE_VERSION = 1
_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"


class KernelConfig(NamedTuple):
    """One tiling point: hashable, so it can ride as a jit static arg."""

    row_block: int
    method: str = DEFAULT_PROJ_METHOD
    iters: int = DEFAULT_BISECT_ITERS

    def to_dict(self) -> dict:
        return {"row_block": self.row_block, "method": self.method,
                "iters": self.iters}


# process-local state: in-memory table view, hit/miss/measurement/rule
# counters, and the last (row_block, rows, padded lanes) resolved
_table: Optional[dict] = None
_table_path: Optional[str] = None
_stats: dict = {"hits": 0, "misses": 0, "measurements": 0, "rule": 0,
                "last": None}


# ------------------------------------------------------------ shape buckets --
def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def lane_pad(l: int) -> int:
    """Lane count after padding to the vector-lane floor."""
    return max(LANE_FLOOR, ((l + LANE_FLOOR - 1) // LANE_FLOOR) * LANE_FLOOR)


def shape_bucket(n: int, l: int) -> tuple[int, int]:
    """(row bucket, lane bucket): rows to the next power of two (>= the
    sublane floor), lanes to the padded lane count — the shapes the kernels
    actually run after padding, so nearby problem sizes share a winner."""
    return max(SUBLANE_FLOOR, _next_pow2(n)), lane_pad(l)


def cache_key(kernel: str, n: int, l: int, platform: Optional[str] = None) -> str:
    nb, lb = shape_bucket(n, l)
    plat = platform or jax.default_backend()
    return f"{kernel}|N{nb}xL{lb}|{plat}|jax{jax.__version__}"


def sort_lanes(l: int) -> int:
    """Lanes of the in-kernel sort: 2 breakpoints a padded lane, to pow2."""
    return _next_pow2(2 * lane_pad(l))


def vmem_bytes(row_block: int, l: int) -> int:
    """Scoped VMEM the fused sortscan step takes at this row block over
    ``l`` lanes: the double-buffered operand blocks and the sort's
    temporaries grow with row block x sort lanes, plus a part that grows
    with the sort lanes squared. An upper bound on what the v5e compiler
    reported at every point measured (PERF.md); the projection kernels
    hold fewer operands, so it bounds them too."""
    p = sort_lanes(l)
    return (VMEM_BYTES_PER_ROW_LANE * row_block * p
            + p * p // VMEM_LANE_SQ_DIVISOR)


def shape_rule(n: int, l: int) -> KernelConfig:
    """The tiling dispatch runs when no tuned entry exists: the widest row
    block that stays within the row bucket, fits ``VMEM_BUDGET``, keeps
    the sort's tile within ``SORT_TILE_MAX``, and leaves at least
    ``MIN_GRID_STEPS`` grid steps for the pipeline to overlap each block's
    copy with the previous block's sort; the smallest block when none
    does. Deterministic in (n, l), counted as ``rule``."""
    nb, _ = shape_bucket(n, l)
    fits = [rb for rb in ROW_BLOCKS
            if rb <= nb and vmem_bytes(rb, l) <= VMEM_BUDGET
            and rb * sort_lanes(l) <= SORT_TILE_MAX
            and -(-n // rb) >= MIN_GRID_STEPS]
    cfg = KernelConfig(max(fits, default=ROW_BLOCKS[0]))
    _stats["rule"] += 1
    _stats["last"] = (cfg.row_block, n, lane_pad(l))
    return cfg


# ---------------------------------------------------------- candidate space --
def candidates(
    kernel: str,
    n: int,
    l: int,
    methods: Sequence[str] = (DEFAULT_PROJ_METHOD,),
) -> list[KernelConfig]:
    """Legal tiling configs for a packed (n rows, l lanes) problem.

    Row blocks beyond the padded row count only add padding, so they are
    capped at the row bucket; sortscan candidates additionally respect the
    VMEM budget (``vmem_bytes``). The bisect method enumerates its
    iteration count too.
    """
    nb, lb = shape_bucket(n, l)
    out: list[KernelConfig] = []
    for method in methods:
        if method not in PROJ_METHODS:
            raise ValueError(f"method must be in {PROJ_METHODS}: {method!r}")
        for rb in ROW_BLOCKS:
            if rb > nb:
                continue
            if method == "sortscan":
                if vmem_bytes(rb, lb) > VMEM_BUDGET:
                    continue
                out.append(KernelConfig(rb, "sortscan", 0))
            else:
                out.extend(KernelConfig(rb, "bisect", it) for it in BISECT_ITERS)
    if not out:  # degenerate shapes still get the smallest legal tile
        out = [KernelConfig(SUBLANE_FLOOR, methods[0],
                            0 if methods[0] == "sortscan"
                            else DEFAULT_BISECT_ITERS)]
    return out


# ------------------------------------------------------------ on-disk table --
def cache_path() -> Optional[str]:
    """The on-disk table under ``$REPRO_AUTOTUNE_CACHE``, or None when the
    variable is unset: dispatch then runs ``shape_rule``'s tiling and the
    compiled program depends on no file outside the checkout."""
    env = os.environ.get(_CACHE_ENV)
    return os.path.join(env, "autotune.json") if env else None


def reset_cache() -> None:
    """Drop the in-memory table view (tests; next lookup re-reads disk)."""
    global _table, _table_path
    _table = None
    _table_path = None


def reset_stats() -> None:
    _stats.update(hits=0, misses=0, measurements=0, rule=0, last=None)


def cache_stats() -> dict:
    """Counters since the last reset: table ``hits`` and ``misses``,
    ``measurements`` taken, resolutions the shape ``rule`` answered, and
    the ``last`` (row_block, rows, padded lanes) resolved, or None."""
    return dict(_stats)


def measurement_count() -> int:
    return _stats["measurements"]


def _valid_entry(ent: object) -> Optional[KernelConfig]:
    """Parse one table entry defensively: anything malformed is a miss."""
    if not isinstance(ent, dict):
        return None
    rb, method, iters = ent.get("row_block"), ent.get("method"), ent.get("iters")
    if not isinstance(rb, int) or rb not in ROW_BLOCKS:
        return None
    if method not in PROJ_METHODS:
        return None
    if not isinstance(iters, int) or iters < 0 or iters > 64:
        return None
    return KernelConfig(rb, method, iters)


def _load_table() -> dict:
    """The on-disk table, re-read when the path changes; {} on any damage."""
    global _table, _table_path
    path = cache_path()
    if path is None:
        return {}
    if _table is not None and _table_path == path:
        return _table
    table: dict = {}
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict) and raw.get("version") == TABLE_VERSION \
                and isinstance(raw.get("entries"), dict):
            table = raw["entries"]
    except (OSError, ValueError):
        table = {}
    _table, _table_path = table, path
    return table


def lookup(kernel: str, n: int, l: int) -> Optional[KernelConfig]:
    """The cached winner for this shape bucket, or None (miss). Corrupt and
    stale entries (wrong schema, illegal values, other platform/jax version
    — those live under different keys) all read as misses."""
    return _valid_entry(_load_table().get(cache_key(kernel, n, l)))


def resolve(kernel: str, n: int, l: int) -> KernelConfig:
    """Dispatch-time tiling resolution: cached winner or ``shape_rule``.

    Never measures and never touches devices — safe inside jit tracing,
    where ``kernels.ops`` calls it on static shapes.
    """
    cfg = lookup(kernel, n, l)
    if cfg is None:
        _stats["misses"] += 1
        return shape_rule(n, l)
    _stats["hits"] += 1
    _stats["last"] = (cfg.row_block, n, lane_pad(l))
    return cfg


def _store(kernel: str, n: int, l: int, cfg: KernelConfig,
           us: float, measured: dict) -> None:
    """Publish a winner: read-modify-write the table through the hardened
    atomic JSON path, then refresh the in-memory view."""
    path = cache_path()
    if path is None:
        raise ValueError(
            f"set {_CACHE_ENV} to the directory that should hold tuned tilings"
        )
    try:
        with open(path) as f:
            raw = json.load(f)
        if not (isinstance(raw, dict) and raw.get("version") == TABLE_VERSION
                and isinstance(raw.get("entries"), dict)):
            raw = {"version": TABLE_VERSION, "entries": {}}
    except (OSError, ValueError):
        raw = {"version": TABLE_VERSION, "entries": {}}
    raw["entries"][cache_key(kernel, n, l)] = {
        **cfg.to_dict(),
        "us": round(float(us), 3),
        "measured": {k: round(float(v), 3) for k, v in measured.items()},
    }
    ckpt.atomic_write_json(path, raw)
    reset_cache()


# ------------------------------------------------------------- measurement --
def _bench_operands(kernel: str, n: int, l: int):
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), n), l)
    kz, ka, kc = jax.random.split(key, 3)
    z = jax.random.normal(kz, (n, l)) * 5.0
    a = jax.random.uniform(ka, (n, l), minval=0.1, maxval=4.0)
    mask = jnp.ones((n, l))
    c = jax.random.uniform(kc, (n,), minval=0.5, maxval=8.0)
    if kernel == "proj":
        return (z, a, mask, c)
    x = (jax.random.uniform(kz, (n, l)) < 0.7).astype(jnp.float32)
    kstar = (jax.random.uniform(ka, (n, l)) < 0.2).astype(jnp.float32)
    from repro.kernels import oga_step as _og

    scal = _og.pack_scal(
        jnp.full((n,), 1.2), jnp.full((n,), 0.4), c,
        jnp.asarray([i % 4 for i in range(n)], jnp.float32),
        jnp.full((n,), 0.5),
    )
    return (z, a, mask, x, kstar, scal)


def _measure_config(
    kernel: str, cfg: KernelConfig, operands, repeats: int
) -> float:
    """Wall-time one candidate (us/call), compile time excluded: warm until
    ``CompilationCounter`` reports no new XLA compiles, then take the best
    of ``repeats`` timed calls. Pallas runs in interpret mode off-TPU —
    there the grid-iteration count still dominates, so tile choice is a
    real (if interpreter-scaled) signal; on TPU the same path times the
    compiled kernel."""
    from repro.compat import CompilationCounter
    from repro.kernels import oga_step as _og
    from repro.kernels import proj_bisect as _pb
    from repro.kernels import sortscan as _ss

    interpret = jax.default_backend() != "tpu"
    if kernel == "proj":
        if cfg.method == "sortscan":
            fn = lambda ops_: _ss.proj_sortscan(
                *ops_, row_block=cfg.row_block, interpret=interpret)
        else:
            fn = lambda ops_: _pb.proj_bisect(
                *ops_, row_block=cfg.row_block, iters=cfg.iters,
                interpret=interpret)
    elif kernel == "oga_step":
        fn = lambda ops_: _og.oga_step_fused(
            *ops_, method=cfg.method, row_block=cfg.row_block,
            iters=cfg.iters or DEFAULT_BISECT_ITERS, interpret=interpret)
    else:
        raise ValueError(f"unknown kernel {kernel!r}")

    _stats["measurements"] += 1
    for _ in range(3):  # warm out of the compile path
        with CompilationCounter() as cc:
            jax.block_until_ready(fn(operands))
        if cc.count == 0:
            break
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(operands))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def tune(
    kernel: str,
    n: int,
    l: int,
    *,
    methods: Sequence[str] = (DEFAULT_PROJ_METHOD,),
    cands: Optional[Sequence[KernelConfig]] = None,
    measure: Optional[Callable[[KernelConfig], float]] = None,
    repeats: int = 10,
    store: bool = True,
) -> tuple[KernelConfig, dict[str, float]]:
    """Benchmark every candidate tiling for this shape and cache the winner.

    ``measure`` may be injected (tests: a fixed measurement table makes the
    winner deterministic); the default harness builds seeded operands once
    and times each candidate with compile exclusion. Ties break toward the
    earlier candidate in enumeration order, so a fixed measurement table
    always yields the same winner. ``store=False`` measures without
    publishing (the bench uses it for A/B-only method sweeps).
    Returns (winner, {config-label: us}).
    """
    cfg_list = list(cands) if cands is not None else candidates(
        kernel, n, l, methods=methods)
    if measure is None:
        operands = _bench_operands(kernel, n, l)
        measure = lambda cfg: _measure_config(kernel, cfg, operands, repeats)
    measured: dict[str, float] = {}
    best_cfg, best_us = None, float("inf")
    for cfg in cfg_list:
        us = float(measure(cfg))
        measured[f"rb{cfg.row_block}-{cfg.method}" +
                 (f"-it{cfg.iters}" if cfg.method == "bisect" else "")] = us
        if us < best_us:
            best_cfg, best_us = cfg, us
    assert best_cfg is not None
    if store:
        _store(kernel, n, l, best_cfg, best_us, measured)
    return best_cfg, measured
