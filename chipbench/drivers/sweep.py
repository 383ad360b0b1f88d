"""The streamed what-if sweep: ``sweep.run_grid_stream`` driven as
``sweep.sweep_stream`` drives it (every algorithm, donated chunks, one
summary per chunk, each committed to a checkpoint store where one is kept).

Traffic keys:

``grid_seeds``, ``eta0s``, ``chunk``
    the grid: ``grid_seeds`` trace seeds drawn from ``--seed``, times
    ``eta0s``, streamed in chunks of ``chunk`` points. One call is one chunk.
``check_configs``
    how many of the window's deployments, drawn from the seed, the check
    compares with the reference.
``mode``, ``sharded``, ``backend``, ``trace_backend``, ``prefetch``, ``queue_depth``, ``rate_floor``
    passed to ``run_grid_stream`` as they are; left out, its defaults hold.
``fault_policy``
    the fields of ``lifecycle.FaultPolicy``.
``checkpoint``
    true: commit each chunk's summary to a ``SweepCheckpoint`` in a fresh
    directory under ``TMPDIR``, as ``sweep_stream(checkpoint_dir=...)`` does.

The check compares every algorithm's rewards in slot mode. A driver file
for another mode brings its own comparison, ``run(..., compare=...)``:
``compare(config, rows, control, info) -> {name: number}``, ``rows`` being
the compared deployments' grid points, specs, outputs and summaries.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

import drive
import reference

STREAM_OPTIONS = ("mode", "sharded", "backend", "trace_backend", "prefetch",
                  "queue_depth", "rate_floor")
SPEC_KEYS = ("mask", "a", "c", "alpha", "beta", "kinds")


def compare_slot(config: dict, rows: list[dict], control: bool,
                 info: dict) -> dict:
    """The compared deployments' specs, every algorithm's per-slot rewards
    and the program's own summary (``sweep.summarize``), against the
    reference rebuilt from each deployment's seed."""
    tcs = [dict(config, seed=r["point"].cfg.seed) for r in rows]
    spec, x = reference.device_traces(tcs, config["templates"])
    spec = {k: np.asarray(v) for k, v in spec.items()}
    eta0 = [r["point"].eta0 for r in rows]
    decay = [r["point"].decay for r in rows]
    ref, untied = drive.reference_rewards(spec, x, eta0, decay, control=False)
    if control:
        prog_spec = {k: v if k == "kinds" else drive.bf16(v)
                     for k, v in spec.items()}
        out = drive.control_gaps(spec, x, eta0, decay, ref, untied, info)
    else:
        prog_spec = {k: np.stack([getattr(r["spec"], k) for r in rows])
                     for k in SPEC_KEYS}
        prog = {n: np.stack([r["out"][n] for r in rows])
                for n in drive.ALGORITHMS}
        avg = {n: np.stack([r["summary"][f"avg/{n}"] for r in rows])
               for n in drive.ALGORITHMS}
        gain = {n: np.stack([r["summary"][f"improvement_pct/{n}"]
                             for r in rows]) for n in reference.HEURISTICS}
        out = drive.reward_gaps(prog, avg, gain, ref, untied, info)
    return {"spec_gap": max(drive.rel_gap(prog_spec[k], spec[k])
                            for k in spec), **out}


COMPARE = {"slot": compare_slot}


def run(config, traffic, *, seed, seconds, window, devices, compare=None):
    import jax
    from repro.sched import lifecycle
    from repro.sched import sweep as sw

    window.mark("imports")
    opts = {k: traffic[k] for k in STREAM_OPTIONS if k in traffic}
    mode = opts.get("mode", "slot")
    compare = compare or COMPARE.get(mode)
    if compare is None:
        raise ValueError(f"the sweep driver has no reference for mode "
                         f"{mode!r}: a driver file that brings one calls "
                         f"run(..., compare=...)")
    opts["fault_policy"] = lifecycle.FaultPolicy(
        **traffic.get("fault_policy", {}))
    chunk = traffic["chunk"]
    points = sw.make_grid(
        drive.trace_config(config), eta0s=traffic["eta0s"],
        decays=(config["oga"]["decay"],),
        seeds=[int(s) for s in drive.derived_seeds(seed, traffic["grid_seeds"])])
    ckpt_dir = ckpt = None
    if traffic.get("checkpoint"):
        ckpt_dir = tempfile.mkdtemp(prefix="chipbench-ckpt-")
        ckpt = sw.SweepCheckpoint(
            ckpt_dir, points, drive.ALGORITHMS, chunk_size=chunk,
            **{k: v for k, v in opts.items() if k not in
               ("sharded", "prefetch")})

    def summarize(out, batch):
        if mode == "lifecycle":
            return sw.summarize_lifecycle(out, batch)
        return sw.summarize(out)

    stats = {}
    it = sw.run_grid_stream(points, drive.ALGORITHMS, chunk_size=chunk,
                            donate=True, stats=stats, checkpoint=ckpt, **opts)
    commits = 0

    def call():
        nonlocal commits
        sl, batch, out = next(it)
        jax.block_until_ready(out)
        summary = {k: np.asarray(v) for k, v in summarize(out, batch).items()}
        if ckpt is not None:
            ckpt.commit(sl.start // chunk, summary)
            commits += 1
        return sl, batch.spec, summary, out

    window.mark("inputs")
    call()  # set-up: compiles or loads, and runs, every program of a chunk
    wait0, commits0 = stats.get("chunk_wait_s", 0.0), commits
    done = []
    window.open()
    while window.elapsed() < seconds:
        with window.span("call"):
            done.append(call())
        t_last = time.perf_counter()
    window.close(t_last)
    it.close()
    configs = sum(sl.stop - sl.start for sl, *_ in done)
    failed = sum(int(np.sum(~np.isfinite(np.stack(list(summary.values())))
                            .all(axis=0))) for _, _, summary, _ in done)
    picks = sorted(drive.sample_rng(seed).choice(
        configs, size=min(traffic["check_configs"], configs), replace=False))
    where = [(i, r) for i, (sl, *_) in enumerate(done)
             for r in range(sl.stop - sl.start)]
    held = []

    def release():
        nonlocal done
        for i, r in (where[p] for p in picks):
            sl, spec, summary, out = done[i]
            row = lambda leaf: np.asarray(leaf)[r]
            held.append({"point": points[sl.start + r],
                         "spec": jax.tree.map(row, spec),
                         "out": {n: jax.tree.map(row, v)
                                 for n, v in out.items()},
                         "summary": {k: v[r] for k, v in summary.items()}})
        done = None
        if ckpt_dir:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        gc.collect()

    L, R, K = (config[k] for k in ("L", "R", "K"))
    info = {}
    return drive.Run(
        end_to_end={"scenario_slots_per_s":
                    configs * config["T"] / window.seconds},
        stats={"window_s": window.seconds, "calls": len(done),
               "configs": configs, "oga_decisions": configs * config["T"],
               "oga_shape": (L, R, K),
               "chunk_wait_s": stats.get("chunk_wait_s", 0.0) - wait0,
               "checkpoint_commits": commits - commits0},
        attempted=configs, failed=failed, release=release,
        check=lambda control=False: compare(config, held, control, info),
        info=info)
