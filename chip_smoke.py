"""Chip smoke test: drive the OGASched scheduler's main path once on a TPU
and check what comes out against the repo's own references.

    python chip_smoke.py              # one chip: phases 1-5 below
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

One process, no children. Each phase prints one JSON line with its wall
times (``first_s``: the first call, compilation included; ``steady_s``: the
same call again) and its parity gaps; any failed check raises and the
script exits non-zero. Only when every phase passed is the last line of
standard output ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``. Without a TPU the script exits non-zero before it computes
anything. JAX's persistent compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.

Phases on one chip:
  1. device    the fused OGA backend is the Pallas kernel, and the lowered
               OGASched step holds the Mosaic kernel (``tpu_custom_call``).
  2. fig5      the paper's Fig. 5 deployment (L=100 job types, R=1024
               instances, K=6 resources, T=2000 slots) through
               ``simulator.run_all``: fused backend vs the reference backend.
  3. tab2_sweep  a 256-point (seed x eta0) streamed sweep of the Tab. 2
               deployment (L=10, R=128, K=6, T=500) through
               ``sweep.sweep_stream``, every algorithm, device traces,
               checkpointed; chunks after the first compile nothing, and
               OGASched's averages match the reference backend.
  4. lifecycle Tab. 2 with multi-slot jobs and server failures through
               ``run_all(mode="lifecycle")``: fused vs reference JCT and
               goodput, and every accepted job is accounted for.
  5. kernel    one fused OGA step on 4096 rows at L=10 and L=200 against
               the float64 numpy projection oracle.

With ``--chips 4``: the Tab. 2 grid of 256 points through
``sweep.run_grid_sharded`` on a 4-chip mesh, in slot mode (every
algorithm) and in lifecycle mode with faults (OGASched and DRF), against
``sweep.run_grid`` on one chip: outputs must span the 4 chips and agree
(docs/sweeps.md).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import ogasched  # noqa: E402
from repro.kernels import oga_step, ops, ref  # noqa: E402
from repro.sched import lifecycle, simulator, sweep, trace  # noqa: E402

OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# Parity bounds, each met by the same comparison on XLA:CPU (fused = the
# packed-row jnp path there). Both backends project exactly; they differ
# only in f32 summation order, and the online learner carries that
# rounding forward through T slots.
FIG5_REL_GAP = 1e-4        # per-slot reward, fused vs reference
SWEEP_REL_GAP = 1e-4       # per-config average reward
LIFECYCLE_REL_GAP = 1e-2   # JCT mean and goodput
KERNEL_ABS_GAP = 1e-5      # kernel output vs the float64 oracle
# Sharded vs one-chip sweep, per output leaf, relative to the leaf's scale.
# OGASched is bitwise equal; some heuristics differ in the last bits once a
# device holds more than one grid row (their reductions reassociate with
# the batch size): on XLA:CPU up to 2e-5 on lifecycle work_done, whose
# remaining work is a difference of large numbers.
SHARDED_REL_GAP = 1e-4

FIG5 = trace.TraceConfig(T=2000, L=100, R=1024, K=6, seed=7, contention=5.0,
                         rho=0.95, beta_range=(0.01, 0.015))
TAB2 = trace.TraceConfig(T=500)  # L=10, R=128, K=6: the paper's Tab. 2
SWEEP_SEEDS = range(64)
# up to the paper's eta0=25: at 50 the learner amplifies last-bit
# differences between the backends into per-slot gaps of up to 8% (CPU)
SWEEP_ETA0S = (2.5, 5.0, 10.0, 25.0)
CHUNK = 64
FAULTS = trace.FaultConfig(fail_rate=0.02, fail_frac=0.3, repair_mean=40.0)
FAULT_POLICY = lifecycle.FaultPolicy(max_retries=3, preserve_work=True)
LIFE = dataclasses.replace(TAB2, work_mean=600.0, faults=FAULTS)
KERNEL_ROWS = 4096


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def walled(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def rel_gap(got, want, floor: float = 1.0) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), floor)))


def assert_mosaic(lowered, what: str) -> None:
    """The lowered program holds the Pallas TPU kernel: no interpret mode
    and no jnp fallback ran in its place."""
    check("tpu_custom_call" in lowered.as_text(),
          f"{what}: no tpu_custom_call in the lowered program")


# ------------------------------------------------------------ one chip --
def phase_device() -> None:
    prov = ops.backend_provenance()
    check(prov["fused_impl"] == "pallas",
          f"fused backend runs {prov['fused_impl']!r}, not the Pallas kernel")
    spec, arrivals = trace.make(dataclasses.replace(FIG5, T=4))
    assert_mosaic(ogasched.run.lower(spec, arrivals, 2.0), "ogasched.run")
    log("device", **prov)


def phase_fig5(cfg=FIG5) -> None:
    spec, arrivals = trace.make(cfg)
    assert_mosaic(ogasched.run.lower(spec, arrivals[:4], 2.0), "fig5 ogasched")
    kw = dict(algorithms=("ogasched",), eta0=2.0, decay=0.9995)
    fused, first_s = walled(lambda: simulator.run_all(cfg, **kw)["ogasched"])
    fused, steady_s = walled(lambda: simulator.run_all(cfg, **kw)["ogasched"])
    refr, ref_s = walled(lambda: simulator.run_all(
        cfg, backend="reference", **kw)["ogasched"])
    gap = rel_gap(fused.rewards, refr.rewards)
    log("fig5", first_s=first_s, steady_s=steady_s, reference_s=ref_s,
        slots=cfg.T, avg_reward_fused=fused.avg_reward,
        avg_reward_reference=refr.avg_reward, max_slot_rel_gap=gap,
        bound=FIG5_REL_GAP)
    check(np.isfinite(fused.rewards).all(), "fig5: non-finite rewards")
    check(gap <= FIG5_REL_GAP, f"fig5: fused vs reference gap {gap}")


def phase_tab2_sweep(base=TAB2, seeds=SWEEP_SEEDS, eta0s=SWEEP_ETA0S,
                     chunk=CHUNK) -> None:
    points = sweep.make_grid(base, eta0s=eta0s, seeds=seeds)
    kw = dict(chunk_size=chunk, trace_backend="device")
    first = sweep.build_batch(points[:chunk], trace_backend="device")
    y = jnp.zeros((chunk, base.L, base.R, base.K), jnp.float32)
    assert_mosaic(
        jax.jit(ops.oga_update_batch).lower(
            first.spec, y, first.arrivals[:, 0], first.eta0),
        "sweep chunk OGA step",
    )
    _, first_s = walled(lambda: sweep.sweep_stream(points[:chunk], **kw))
    ckpt_dir = os.path.join(OUT_DIR, "sweep_ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    with compat.CompilationCounter() as cc:
        summ, steady_s = walled(lambda: sweep.sweep_stream(
            points, checkpoint_dir=ckpt_dir, **kw))
    refr = sweep.sweep_stream(points[:chunk], ("ogasched",),
                              backend="reference", **kw)
    gap = rel_gap(summ["avg/ogasched"][:chunk], refr["avg/ogasched"])
    n_cfg = len(points)
    log("tab2_sweep", first_s=first_s, steady_s=steady_s, points=n_cfg,
        chunk=chunk, compiles_after_first_chunk=cc.count,
        scenario_slots_per_s=n_cfg * base.T / steady_s,
        mean_avg_ogasched=float(np.mean(summ["avg/ogasched"])),
        mean_improvement_pct={
            k.split("/")[1]: float(np.mean(v)) for k, v in summ.items()
            if k.startswith("improvement_pct/")},
        max_rel_gap_vs_reference=gap, bound=SWEEP_REL_GAP)
    check(all(v.shape == (n_cfg,) and np.isfinite(v).all()
              for v in summ.values()), "tab2_sweep: bad summary rows")
    check(cc.count == 0, f"tab2_sweep: {cc.count} compiles after chunk 0")
    check(gap <= SWEEP_REL_GAP, f"tab2_sweep: fused vs reference gap {gap}")


def phase_lifecycle(cfg=LIFE) -> None:
    kw = dict(mode="lifecycle", algorithms=("ogasched",),
              fault_policy=FAULT_POLICY)
    spec, arrivals = trace.make(cfg)
    works, faults = trace.build_works(cfg), trace.build_faults(cfg)
    assert_mosaic(lifecycle.run.lower(
        spec, arrivals, works, faults=faults, fault_policy=FAULT_POLICY),
        "lifecycle.run")
    fused, first_s = walled(lambda: simulator.run_all(cfg, **kw)["ogasched"])
    fused, steady_s = walled(lambda: simulator.run_all(cfg, **kw)["ogasched"])
    refr = simulator.run_all(cfg, backend="reference", **kw)["ogasched"]
    m, mr = fused.lifecycle, refr.lifecycle
    gaps = {k: rel_gap(m[k], mr[k]) for k in ("jct_mean", "goodput")}
    # job conservation on the trace of the same fused run
    tr = lifecycle.run(spec, arrivals, works, faults=faults,
                       fault_policy=FAULT_POLICY)
    books = dict(
        accepted=int(np.sum(np.asarray(arrivals) > 0)
                     - np.asarray(tr.dropped)[-1]),
        completed=int(np.asarray(tr.departed).sum()),
        running=int(np.asarray(tr.running)[-1].sum()),
        queued=int(np.asarray(tr.q_depth)[-1].sum()),
        dropped=int(np.asarray(tr.rdropped)[-1]),
    )
    log("lifecycle", first_s=first_s, steady_s=steady_s, slots=cfg.T,
        jct_mean=m["jct_mean"], jct_mean_reference=mr["jct_mean"],
        goodput=m["goodput"], goodput_reference=mr["goodput"],
        evictions=m["evictions"], rel_gaps=gaps, bound=LIFECYCLE_REL_GAP,
        **books)
    check(m["evictions"] > 0, "lifecycle: the fault stream evicted nothing")
    check(max(gaps.values()) <= LIFECYCLE_REL_GAP,
          f"lifecycle: fused vs reference gaps {gaps}")
    check(books["accepted"] == books["completed"] + books["running"]
          + books["queued"] + books["dropped"],
          f"lifecycle: job books do not balance {books}")


def _ascent_f64(y, a, mask, x, kstar, scal):
    """The kernel's eq. 30 ascent point, in float64 numpy."""
    y, a, mask, x, kstar, scal = (np.asarray(t, np.float64)
                                  for t in (y, a, mask, x, kstar, scal))
    alpha, beta, _, kind, eta = (scal[:, i:i + 1] for i in range(5))
    ym = np.maximum(y * mask, 0.0)
    g = np.select(
        [kind == 0, kind == 1, kind == 2, kind == 3],
        [alpha + 0 * ym, alpha / (1 + ym), 1 / (ym + alpha) ** 2,
         alpha / (2 * np.sqrt(ym + 1))],
    )
    return y + eta * x * (g - beta * kstar) * mask


def phase_kernel(rows=KERNEL_ROWS, widths=(10, 200)) -> None:
    for L in widths:
        k = jax.random.split(jax.random.PRNGKey(L), 6)
        y = jax.random.uniform(k[0], (rows, L), maxval=3.0)
        a = jax.random.uniform(k[1], (rows, L), minval=0.1, maxval=4.0)
        mask = (jax.random.uniform(k[2], (rows, L)) < 0.8).astype(jnp.float32)
        x = (jax.random.uniform(k[3], (rows, L)) < 0.7).astype(jnp.float32)
        kstar = (jax.random.uniform(k[4], (rows, L)) < 0.2).astype(jnp.float32)
        c = jax.random.uniform(k[5], (rows,), minval=0.5, maxval=8.0)
        scal = oga_step.pack_scal(
            jnp.full((rows,), 1.2), jnp.full((rows,), 0.4), c,
            jnp.asarray(np.arange(rows) % 4, jnp.float32),
            jnp.full((rows,), 5.0),
        )
        args = (y, a, mask, x, kstar, scal)
        step = jax.jit(ops.oga_step_fused)
        assert_mosaic(step.lower(*args), f"oga_step_fused L={L}")
        got, first_s = walled(lambda: step(*args))
        got, steady_s = walled(lambda: step(*args))
        want = ref.proj_rows_exact_np(_ascent_f64(*args), a, mask, c)
        err = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
        log("kernel", L=L, rows=rows, first_s=first_s, steady_s=steady_s,
            max_abs_err_vs_f64=err, bound=KERNEL_ABS_GAP)
        check(err <= KERNEL_ABS_GAP, f"kernel L={L}: error {err}")


# --------------------------------------------------------- four chips --
def _agreement(a, b) -> tuple[bool, float]:
    """(bitwise equal, largest float gap relative to its leaf's scale).
    Integer and boolean leaves (admissions, departures, queue depths) must
    match exactly for the gap to be finite."""
    bitwise, gap = True, 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if np.array_equal(x, y):
            continue
        bitwise = False
        if not np.issubdtype(x.dtype, np.floating):
            return False, float("inf")
        x, y = x.astype(np.float64), y.astype(np.float64)
        gap = max(gap, float(np.max(np.abs(x - y))
                             / max(float(np.max(np.abs(x))), 1.0)))
    return bitwise, gap


def phase_sharded(devices, base=TAB2, life=LIFE, seeds=SWEEP_SEEDS,
                  eta0s=SWEEP_ETA0S) -> None:
    mesh = compat.grid_mesh(devices=devices)
    check(mesh is not None and mesh.size == 4
          and all(d.platform == "tpu" for d in mesh.devices.flat),
          f"need a mesh of 4 TPU devices, got {devices}")
    slot_pts = sweep.make_grid(base, eta0s=eta0s, seeds=seeds)
    life_pts = sweep.make_grid(life, eta0s=eta0s, seeds=seeds)
    # lifecycle runs OGASched and one budgeted heuristic: the two kinds of
    # path, at a third of the chip time of every algorithm
    for mode, points, algorithms in (
            ("slot", slot_pts, sweep.ALGORITHMS),
            ("lifecycle", life_pts, ("ogasched", "drf"))):
        batch = sweep.build_batch(points, mode)
        kw = dict(algorithms=algorithms, mode=mode,
                  fault_policy=FAULT_POLICY)
        sh, sharded_s = walled(
            lambda: sweep.run_grid_sharded(batch, mesh=mesh, **kw))
        one, one_chip_s = walled(lambda: sweep.run_grid(batch, **kw))
        spans = {len(leaf.sharding.device_set)
                 for leaf in jax.tree.leaves(sh)}
        agree = {name: _agreement(sh[name], one[name]) for name in sh}
        log(f"sharded_{mode}", points=len(points), slots=base.T,
            sharded_s=sharded_s, one_chip_s=one_chip_s,
            output_device_spans=sorted(spans),
            bitwise_equal={n: a[0] for n, a in agree.items()},
            max_rel_gap={n: a[1] for n, a in agree.items()},
            bound=SHARDED_REL_GAP)
        check(spans == {4}, f"{mode}: outputs span {spans} devices, not 4")
        check(all(gap <= SHARDED_REL_GAP for _, gap in agree.values()),
              f"{mode}: sharded vs one-chip results {agree}")


# ----------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devices)} "
              f"{dev.platform} device(s)", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache = compat.use_repo_compile_cache()
    os.makedirs(OUT_DIR, exist_ok=True)
    log("start", device_kind=dev.device_kind, devices=len(devices),
        chips=args.chips, jax=jax.__version__, compile_cache=cache)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(devices[:4])
    else:
        phase_device()
        phase_fig5()
        phase_tab2_sweep()
        phase_lifecycle()
        phase_kernel()
    log("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
