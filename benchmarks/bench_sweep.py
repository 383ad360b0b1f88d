"""Scenario-sweep throughput: resident vmapped grids vs the streaming driver.

Measures configs/sec at several grid sizes for ``sweep.run_grid`` (whole
grid resident, host-generated traces) and the production streaming path
(``sweep.run_grid_stream``: device-synthesized traces + double-buffered
chunk prefetch), checks the streamed host path still reorganizes the
resident computation exactly, and emits machine-readable records so the
perf trajectory is tracked across PRs (benchmarks/run.py writes them to
``BENCH_sweep.json``). Timed regions include trace generation and the
summary reduction — the full cost of answering "run this grid".

Per streamed record: ``overlap_ratio`` = 1 - (time this thread stalled
waiting on the chunk pipeline) / wall — 1.0 means chunk prep (trace
synthesis, padding, upload) was fully hidden behind compute. The
``trace_gen`` records give raw host-numpy vs device-jitted generation
throughput at the streaming chunk size; CI gates on streamed >= resident
at G=64 (the acceptance cliff: streamed used to LOSE there, 123 vs 146
configs/s, because every chunk serialized behind host generation).

Full mode adds the acceptance-scale demonstration: a 10,000-config
slot-mode grid and a 2,000-config lifecycle grid through the streaming
path, which never materializes full-grid (G, T, ...) tensors (peak memory
is the chunk plus prefetched chunk inputs; ``sweep.grid_memory_bytes``
quantifies all of it).
"""
from __future__ import annotations

import os
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro import compat
from repro.sched import sweep, trace

# small per-config shape so grid-size scaling (not per-config cost)
# dominates the measurement
CFG = trace.TraceConfig(T=100, L=6, R=16, K=4)
ALGOS = ("ogasched", "fairness")
CHUNK = 64


def _points(G: int) -> list[sweep.SweepPoint]:
    return sweep.make_grid(CFG, seeds=range(G))


def _time_resident(points, mode: str, backend: str = "auto"):
    t0 = time.time()
    batch = sweep.build_batch(points, mode=mode)
    out = sweep.run_grid(batch, ALGOS, mode=mode, backend=backend)
    summ = (
        sweep.summarize_lifecycle(out, batch) if mode == "lifecycle"
        else sweep.summarize(out)
    )
    jax.block_until_ready(jax.tree.leaves(summ))
    return time.time() - t0, summ


def _time_streamed(
    points, mode: str, chunk: int,
    backend: str = "auto", trace_backend: str = "device",
):
    """(wall_s, summary, overlap_ratio, compiles) for the streaming path.

    Drives the REAL ``sweep.run_grid_stream`` (so the CI-gated numbers
    cannot drift from what ``sweep_stream`` actually runs) with its
    ``stats`` telemetry: ``chunk_wait_s`` is the time the driver stalled
    waiting on the prefetched chunk pipeline — trace synthesis/padding/
    upload the background worker failed to hide, NOT dispatch or reduction
    cost. ``overlap_ratio`` = 1 - chunk_wait/wall. ``compiles`` is the
    number of XLA backend compiles the run triggered (None when
    jax.monitoring is unavailable): after warmup every chunk reuses the
    first chunk's executable, so measured runs must report 0 — the CI
    recompile gate enforces exactly that on the streamed records.
    """
    t0 = time.time()
    stats: dict = {}
    parts: dict[str, list[np.ndarray]] = {}
    with compat.CompilationCounter() as cc:
        for _, batch, out in sweep.run_grid_stream(
            points, ALGOS, chunk_size=chunk, mode=mode,
            backend=backend, trace_backend=trace_backend, donate=True,
            stats=stats,
        ):
            summ = (
                sweep.summarize_lifecycle(out, batch) if mode == "lifecycle"
                else sweep.summarize(out)
            )
            for k, v in summ.items():
                parts.setdefault(k, []).append(np.asarray(v))
    wall = time.time() - t0
    summ = {k: np.concatenate(v) for k, v in parts.items()}
    stall = stats.get("chunk_wait_s", 0.0)
    overlap = max(0.0, min(1.0, 1.0 - stall / max(wall, 1e-9)))
    return wall, summ, overlap, cc.count


def _record(name, mode, G, chunk, elapsed, records, backend="fused",
            trace_backend="host", overlap_ratio=None, jit_cache_misses=None):
    mem = sweep.grid_memory_bytes(CFG, G, mode=mode, algorithms=ALGOS)
    peak = sweep.grid_memory_bytes(
        CFG, min(chunk, G) if chunk else G, mode=mode, algorithms=ALGOS,
        prefetch=2 if chunk else 0,
    )
    rec = {
        "name": name,
        "mode": mode,
        "backend": backend,
        "trace_backend": trace_backend,
        "G": G,
        "chunk_size": chunk,
        "elapsed_s": round(elapsed, 4),
        "configs_per_s": round(G / elapsed, 2),
        "resident_bytes_est": mem["total"],
        "streamed_peak_bytes_est": peak["total"],
    }
    if overlap_ratio is not None:
        rec["overlap_ratio"] = round(overlap_ratio, 3)
    if jit_cache_misses is not None:
        rec["jit_cache_misses"] = jit_cache_misses
    records.append(rec)
    emit(
        f"sweep.{name}.{mode}.{backend}.traces={trace_backend}"
        f".G={G}.T={CFG.T}.R={CFG.R}",
        elapsed * 1e6 / G,
        f"configs_per_s={rec['configs_per_s']};"
        f"peak_bytes_est={rec['streamed_peak_bytes_est']}"
        + (f";overlap_ratio={rec['overlap_ratio']}"
           if overlap_ratio is not None else ""),
    )
    return rec


def _bench_trace_gen(records, chunk: int = CHUNK, reps: int = 5):
    """Raw trace-generation throughput, host numpy vs device-jitted, at the
    streaming chunk size (the per-chunk cost the old driver serialized)."""
    cfgs = [p.cfg for p in _points(chunk)]
    out = {}
    for tb in ("host", "device"):
        jax.block_until_ready(jax.tree.leaves(
            trace.make_batch(cfgs, trace_backend=tb)[:2]
        ))  # warm (compile + template upload)
        t0 = time.time()
        for _ in range(reps):
            leaves = jax.tree.leaves(trace.make_batch(cfgs, trace_backend=tb)[:2])
        jax.block_until_ready(leaves)
        el = (time.time() - t0) / reps
        out[tb] = chunk / el
        records.append({
            "name": "trace_gen", "trace_backend": tb, "chunk_size": chunk,
            "configs_per_s": round(out[tb], 2),
        })
        emit(f"sweep.trace_gen.{tb}.chunk={chunk}", el * 1e6 / chunk,
             f"configs_per_s={out[tb]:.1f}")
    ratio = out["device"] / max(out["host"], 1e-9)
    records.append({
        "name": "trace_gen_speedup", "chunk_size": chunk,
        "device_vs_host": round(ratio, 2),
    })
    emit(f"sweep.trace_gen_speedup.chunk={chunk}", 0.0,
         f"device_vs_host={ratio:.2f}")


def _bench_resume(records, G: int = 64, chunk: int = 16):
    """Cost of crash-safety: streamed sweep with per-chunk checkpointing vs
    without, plus the payoff — resuming after losing the newest half of the
    chunk checkpoints recomputes only the missing chunks."""
    pts = _points(G)
    n_chunks = G // chunk

    def _run(ckpt_dir=None):
        t0 = time.time()
        sweep.sweep_stream(
            pts, ALGOS, chunk_size=chunk, checkpoint_dir=ckpt_dir,
        )
        return time.time() - t0

    _run()  # warm this chunk shape
    t_plain = _run()
    with tempfile.TemporaryDirectory() as d:
        t_ckpt = _run(d)
        # preemption: the newest half of the chunk checkpoints is lost
        for s in range(n_chunks // 2, n_chunks):
            for suffix in (".npz", ".json"):
                os.remove(os.path.join(d, f"step_{s:08d}{suffix}"))
        t_resume = _run(d)
    overhead_pct = 100.0 * (t_ckpt - t_plain) / max(t_plain, 1e-9)
    speedup = t_ckpt / max(t_resume, 1e-9)
    records.append({
        "name": "sweep.resume", "mode": "slot", "G": G, "chunk_size": chunk,
        "streamed_s": round(t_plain, 4),
        "checkpointed_s": round(t_ckpt, 4),
        "checkpoint_overhead_pct": round(overhead_pct, 2),
        "resumed_half_s": round(t_resume, 4),
        "resume_speedup": round(speedup, 2),
    })
    emit(
        f"sweep.resume.slot.G={G}.chunk={chunk}", t_ckpt * 1e6 / G,
        f"checkpoint_overhead_pct={overhead_pct:.2f};"
        f"resume_speedup={speedup:.2f}",
    )


def run(quick: bool = True) -> list[dict]:
    records: list[dict] = []

    # warm every measured path once so compile time stays out of the timings
    warm = _points(CHUNK)
    _time_resident(warm, "slot")
    _time_streamed(warm, "slot", CHUNK)
    _, s_host = _time_resident(warm, "slot")
    _, s_stream_host, _, _ = _time_streamed(
        warm, "slot", CHUNK, trace_backend="host"
    )
    for k in s_host:  # streamed host path = pure reorganisation of resident
        np.testing.assert_allclose(s_stream_host[k], s_host[k], err_msg=k)

    # host-vs-device generation throughput at the streaming chunk size
    _bench_trace_gen(records)

    # Resident (host traces — the full-grid baseline) vs the production
    # streamed path (device-synthesized traces + double-buffered prefetch).
    # Measured in interleaved rounds: separate blocks would let a slow
    # machine phase land entirely on one G and fake a trend either way.
    # Acceptance (CI-gated): streamed configs/s >= resident at EVERY G —
    # the PR 4 driver lost at G=64 (123 vs 146) because each chunk stalled
    # behind serial host numpy.
    sizes = (64, 256) if quick else (64, 256, 1024)
    pts = {G: _points(G) for G in sizes}
    for G in sizes:
        _time_resident(pts[G], "slot")  # warm each G's program shape
        _time_streamed(pts[G], "slot", CHUNK)
    rounds = 3
    res_el = {G: 0.0 for G in sizes}
    str_el = {G: 0.0 for G in sizes}
    str_ov = {G: 0.0 for G in sizes}
    str_cc: dict[int, int | None] = {G: 0 for G in sizes}
    for _ in range(rounds):
        for G in sizes:
            t, _ = _time_resident(pts[G], "slot")
            res_el[G] += t
            t, _, ov, cc = _time_streamed(pts[G], "slot", CHUNK)
            str_el[G] += t
            str_ov[G] += ov
            str_cc[G] = None if cc is None else (str_cc[G] or 0) + cc
    fused_cps: dict[int, float] = {}
    for G in sizes:
        _record("resident", "slot", G, 0, res_el[G] / rounds, records)
        rec = _record(
            "streamed", "slot", G, CHUNK, str_el[G] / rounds, records,
            trace_backend="device", overlap_ratio=str_ov[G] / rounds,
            jit_cache_misses=str_cc[G],
        )
        fused_cps[G] = rec["configs_per_s"]

    # the scaling signal, machine-readable: streamed fused throughput at the
    # largest grid relative to the smallest (>= ~1.0 means the PR 3
    # "degrades with G" cliff stays gone)
    gs = sorted(fused_cps)
    if len(gs) >= 2:
        ratio = fused_cps[gs[-1]] / max(fused_cps[gs[0]], 1e-9)
        emit(f"sweep.fused_scaling.G={gs[0]}->G={gs[-1]}", 0.0,
             f"configs_per_s_ratio={ratio:.2f}")
        records.append({
            "name": "sweep.fused_scaling", "mode": "slot",
            "backend": "fused", "G_small": gs[0], "G_large": gs[-1],
            "configs_per_s_ratio": round(ratio, 3),
        })

    # reference-backend A/B at the smallest grid (the PR 3 default path),
    # measured with the same equal-work averaging as the fused rows
    ref_pts = _points(64)
    _time_resident(ref_pts, "slot", backend="reference")  # warm
    reps = max(2, 256 // 64)
    t_ref = sum(
        _time_resident(ref_pts, "slot", backend="reference")[0]
        for _ in range(reps)
    ) / reps
    _record("resident", "slot", 64, 0, t_ref, records, backend="reference")

    # crash-safety cost + resume payoff (BENCH_sweep.json "sweep.resume")
    _bench_resume(records)

    # lifecycle: outputs are ~R*K/1 larger per config; stream a modest grid
    G_life = 32 if quick else 256
    life_pts = _points(G_life)
    _time_streamed(life_pts[:16], "lifecycle", 16)  # warm
    t_life, _, ov_life, cc_life = _time_streamed(life_pts, "lifecycle", 16)
    _record("streamed", "lifecycle", G_life, 16, t_life, records,
            trace_backend="device", overlap_ratio=ov_life,
            jit_cache_misses=cc_life)

    if not quick:
        # acceptance scale: full-grid tensors for these would be resident
        # gigabytes in lifecycle mode; the stream holds one chunk (plus the
        # prefetched next chunk's inputs) at a time. Chunk shapes here are
        # cold (never warmed), so the recompile gate exempts them: misses
        # are reported as provenance, not gated.
        t10k, _, ov, _ = _time_streamed(_points(10_000), "slot", 256)
        _record("streamed", "slot", 10_000, 256, t10k, records,
                trace_backend="device", overlap_ratio=ov)
        t2k, _, ov, _ = _time_streamed(_points(2_000), "lifecycle", 32)
        _record("streamed", "lifecycle", 2_000, 32, t2k, records,
                trace_backend="device", overlap_ratio=ov)

    return records


if __name__ == "__main__":
    import json

    with open("BENCH_sweep.json", "w") as f:
        json.dump(run(), f, indent=2)
