"""The lifecycle sweep: the streamed sweep of ``drivers/sweep.py`` in
``mode: "lifecycle"`` (jobs hold what they are given until their work
drains, behind per-port queues, under capacity faults), checked against
``reference_lifecycle.py``.

The deployment's ``faults`` holds the fields of ``trace.FaultConfig``;
every traffic key is the sweep driver's. To the sweep driver's stats this
driver adds the program's own counters, summed over the window's
deployments and algorithms from the summaries ``sweep.summarize_lifecycle``
returns: ``evictions``, ``fault_drops``, ``queue_drops``, ``completed``.
A deployment counts as failed where an algorithm's per-slot rewards are
not all finite: a lifecycle summary is NaN where nothing departed, which
is no failure.

The check draws ``check_configs`` of the window's deployments and
compares, for each and every algorithm:

``spec_gap``, ``works_gap``, ``faults_gap``
    the deployment, job sizes and capacity multipliers the program
    synthesizes (the chunk's spec; its synthesis rerun for the job sizes
    and faults, which the stream hands to the last program) against the
    reference's rebuilt from the seed: bit for bit.
``lc_slot_gap``
    the widest gap of a slot's admission reward, over the deployment's
    mean reward magnitude, in the untied prefix (the slots before the
    reference's first near-tie, ``reference_lifecycle.untied``), for
    OGASched, DRF and fairness, whose allocations do not turn on an
    order of instances by utilization.
``lc_slot_gap_util_order``
    the same for bin-packing and spreading, whose instance order follows
    each instance's utilization. An instance filled to its capacity by
    two or more jobs keeps a residual of float32 dust whose sign turns
    on the order in which its holdings are summed; a port that takes
    that dust raises the instance's utilization by 1/K and reorders it
    for the ports after it. The events stay the same, but a few units
    of a resource move between instances, so this gap has a limit of its
    own: above what summing the holdings in another order moves.
``event_gap``
    how many admissions, departures and evictions (per port and slot),
    queue-full drop counts and eviction drop counts (per slot) differ from
    the reference's in the untied prefix.
``summary_gap``
    the widest relative gap of the program's summary (jct mean, goodput,
    wasted work, evictions, utilization, completions and drops) from the
    plain reduction of its own per-slot trace.
``feasibility_excess``
    the most that held plus newly granted resources exceed the surviving
    capacity ``c * f_t``, relative to ``1 + c * f_t``, at any slot.
``conservation_gap``
    arrivals not accounted for as dropped at a full queue, completed,
    dropped after eviction, in service or queued at the end.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import drive
import reference_lifecycle as rl

SPEC_KEYS = ("mask", "a", "c", "alpha", "beta", "kinds")
# the per-slot outputs compared, by the program's names (LifecycleTrace)
TRACE_KEYS = ("rewards", "admitted", "departed", "evicted", "dropped",
              "rdropped", "used", "running", "q_depth", "jct", "work_done",
              "wasted")
EVENTS = ("admitted", "departed", "evicted")
COUNTS = ("dropped", "rdropped")
# the heuristics whose instance order follows utilization
UTIL_ORDER = ("binpacking", "spreading")
SUMMARY = ("jct_mean", "goodput", "wasted_work", "evictions", "utilization",
           "completed", "dropped", "fault_drops")
# the program's summaries, summed over a window: stat -> summary metric
COUNTERS = {"evictions": "evictions", "fault_drops": "fault_drops",
            "queue_drops": "dropped", "completed": "completed"}


def plain_summary(tr: dict, c: np.ndarray) -> dict:
    """The summary of one deployment's per-slot trace, reduced in float64:
    tr {event: (T, ...)}, c (R, K) nominal capacities."""
    tr = {k: np.asarray(v, np.float64) for k, v in tr.items()}
    T = tr["rewards"].shape[0]
    dep = tr["departed"] > 0
    return {
        "jct_mean": tr["jct"][dep].mean() if dep.any() else np.nan,
        "goodput": (tr["work_done"].sum() - tr["wasted"].sum()) / T,
        "wasted_work": tr["wasted"].sum(),
        "evictions": tr["evicted"].sum(),
        "utilization": (tr["used"] / np.maximum(c, 1e-9)[None]).mean(),
        "completed": dep.sum(),
        "dropped": tr["dropped"][-1],
        "fault_drops": tr["rdropped"][-1],
    }


def gaps(prog: dict, prog_summary: dict, ref: dict, untied: dict,
         spec: dict, arrivals: np.ndarray, faults: np.ndarray,
         info: dict) -> dict:
    """prog, ref: {algorithm: {event: (G, T, ...)}}; prog_summary:
    {algorithm: {metric: (G,)}}; untied: {algorithm: (G,)} slots compared;
    spec, arrivals, faults: the reference's deployments."""
    G, T = arrivals.shape[:2]
    c_t = spec["c"][:, None].astype(np.float64) * faults[:, :, None]
    slot = dict.fromkeys(("lc_slot_gap", "lc_slot_gap_util_order"), 0.0)
    events = summary = excess = lost = 0.0
    for n, want in ref.items():
        got = {k: np.asarray(v) for k, v in prog[n].items()}
        keep = np.arange(T)[None] < untied[n][:, None]  # (G, T)
        r_got = got["rewards"].astype(np.float64)
        r_want = np.asarray(want["rewards"], np.float64)
        scale = np.maximum(np.mean(np.abs(r_want), axis=1), 1.0)
        gap = "lc_slot_gap_util_order" if n in UTIL_ORDER else "lc_slot_gap"
        slot[gap] = max(slot[gap], float(np.max(
            np.max(np.abs(r_got - r_want) * keep, axis=1) / scale)))
        for k in EVENTS:
            events += float(np.sum((got[k] != np.asarray(want[k]))
                                   * keep[:, :, None]))
        for k in COUNTS:
            events += float(np.sum((got[k] != np.asarray(want[k])) * keep))
        for g in range(G):
            row = {k: v[g] for k, v in got.items()}
            for metric, value in plain_summary(row, spec["c"][g]).items():
                summary = max(summary, drive.rel_gap(
                    prog_summary[n][metric][g], value))
            ended = (row["dropped"][-1] + row["rdropped"][-1]
                     + np.sum(row["departed"]) + np.sum(row["running"][-1])
                     + np.sum(row["q_depth"][-1]))
            lost = max(lost, abs(float(np.sum(arrivals[g] > 0))
                                 - float(ended)))
        over = (got["used"].astype(np.float64) - c_t) / (1 + c_t)
        excess = max(excess, float(np.max(over)))
    share = np.concatenate([u / T for u in untied.values()])
    info["lc_slots_compared"] = float(np.mean(share))
    info["lc_slots_compared_min"] = float(np.min(share))
    return {**slot, "event_gap": events,
            "summary_gap": summary,
            "feasibility_excess": max(excess, 0.0),
            "conservation_gap": lost}


def compare(config: dict, rows: list[dict], control: bool, info: dict, *,
            device: bool, queue_depth: int, rate_floor: float,
            policy: dict) -> dict:
    """The gaps of the module docstring for the compared deployments
    ``rows`` (the sweep driver's: grid point, spec, per-slot outputs and
    summary of each); ``control`` puts the reference computed in bfloat16
    in the program's place."""
    import jax.numpy as jnp

    tcs = [dict(config, seed=r["point"].cfg.seed) for r in rows]
    spec, x, works, faults = rl.traces(tcs, config["templates"], device)
    eta0 = np.asarray([r["point"].eta0 for r in rows], np.float32)
    decay = np.asarray([r["point"].decay for r in rows], np.float32)

    def reference(n, dtype):
        out = rl.run_batch(spec, x, works, faults, eta0, decay, algorithm=n,
                           queue_depth=queue_depth, rate_floor=rate_floor,
                           policy=tuple(sorted(policy.items())), dtype=dtype)
        return {k: np.asarray(v) for k, v in out.items()}

    ref = {n: reference(n, jnp.float32) for n in drive.ALGORITHMS}
    untied = {n: rl.untied(v) for n, v in ref.items()}
    if control:
        prog = {n: {k: v for k, v in reference(n, jnp.bfloat16).items()
                    if k in TRACE_KEYS} for n in drive.ALGORITHMS}
        plain = {n: [plain_summary({e: v[g] for e, v in out.items()},
                                   spec["c"][g]) for g in range(len(rows))]
                 for n, out in prog.items()}
        prog_summary = {n: {k: drive.bf16([p[k] for p in plain[n]])
                            for k in SUMMARY} for n in plain}
        prog_spec = {k: v if k == "kinds" else drive.bf16(v)
                     for k, v in spec.items()}
        prog_works, prog_faults = drive.bf16(works), drive.bf16(faults)
    else:
        from repro.sched import sweep as sw

        prog = {n: {k: np.stack([getattr(r["out"][n], k) for r in rows])
                    for k in TRACE_KEYS} for n in drive.ALGORITHMS}
        prog_summary = {n: {k: np.stack([r["summary"][f"{k}/{n}"]
                                         for r in rows]) for k in SUMMARY}
                        for n in drive.ALGORITHMS}
        prog_spec = {k: np.stack([getattr(r["spec"], k) for r in rows])
                     for k in SPEC_KEYS}
        batch = sw.build_batch([r["point"] for r in rows], mode="lifecycle",
                               trace_backend="device" if device else "host")
        prog_works = np.asarray(batch.works)
        prog_faults = np.asarray(batch.faults)
    out = gaps(prog, prog_summary, ref, untied, spec, x, faults, info)
    return {"spec_gap": max(drive.rel_gap(prog_spec[k], spec[k])
                            for k in spec),
            "works_gap": drive.rel_gap(prog_works, works),
            "faults_gap": drive.rel_gap(prog_faults, faults), **out}


def run(config, traffic, *, seed, seconds, window, devices):
    from repro.sched import lifecycle
    from repro.sched import sweep as sw
    from repro.sched import trace

    program = dict(config, faults=trace.FaultConfig(**config["faults"]))
    n_points = traffic["grid_seeds"] * len(traffic["eta0s"])
    device = sw.resolve_trace_backend(
        traffic.get("trace_backend", "auto"), n_points) == "device"
    policy = dataclasses.asdict(
        lifecycle.FaultPolicy(**traffic.get("fault_policy", {})))
    counts = dict.fromkeys(COUNTERS, 0.0)
    failed = 0
    summarize = sw.summarize_lifecycle

    def counted(traces, batch):
        """The program's summary of a chunk; in the window, its counters
        summed, and the deployments whose rewards are not all finite."""
        nonlocal failed
        out = summarize(traces, batch)
        if window.t0 is not None:
            for stat, metric in COUNTERS.items():
                counts[stat] += float(sum(np.sum(out[f"{metric}/{n}"])
                                          for n in traces))
            finite = [np.isfinite(np.asarray(tr.rewards)).all(axis=1)
                      for tr in traces.values()]
            failed += int(np.sum(~np.all(finite, axis=0)))
        return out

    # the sweep driver runs the window and summarizes each chunk through
    # sweep.summarize_lifecycle: the counters are read where it does
    sweep_driver = drive.load_driver("sweep")
    sw.summarize_lifecycle = counted
    try:
        run = sweep_driver.run(
            program, traffic, seed=seed, seconds=seconds, window=window,
            devices=devices,
            compare=lambda cfg, rows, control, info: compare(
                config, rows, control, info, device=device,
                queue_depth=traffic.get("queue_depth", 8),
                rate_floor=traffic.get("rate_floor", 1e-3), policy=policy))
    finally:
        sw.summarize_lifecycle = summarize
    return dataclasses.replace(run, failed=failed,
                               stats={**run.stats, **counts})
