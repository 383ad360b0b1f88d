"""The operators' path: ``JobManager.step``, one call per slot, under
open-loop arrivals at a fixed slot rate.

Traffic keys: ``rate_hz``, the slot rate: slot i is due ``i / rate_hz``
seconds into the window, and its latency runs from that due time until its
grants are on the host; ``warmup_slots``, the slots stepped in set-up.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import drive
import reference

BOUNDARY = 1e-3  # a chip total this close to a power of two may round either way


def grant_mismatches(grants: list[dict], names: list[str], x: np.ndarray,
                     chips: np.ndarray) -> int:
    """Grants that differ from the reference's, leaving out chip totals
    within BOUNDARY of a power of two, where rounding may go either way."""
    want = reference.pow2_grant(chips)
    edge = np.zeros(chips.shape, bool)
    for n in range(0, 24):
        edge |= np.abs(chips - 2.0 ** n) <= BOUNDARY * 2.0 ** n
    bad = 0
    for t, g in enumerate(grants):
        for l in np.nonzero(x[t] > 0)[0]:
            if g.get(names[l]) != want[t, l] and not edge[t, l]:
                bad += 1
    return bad


def feasibility_excess(y, spec) -> float:
    """How far y leaves its feasible set, as a share of the bound it
    breaks: capacities c (eq. 6), channel caps a (eq. 5), y >= 0."""
    y = np.asarray(y, np.float64)
    m = spec["mask"][:, :, None]
    a = np.asarray(spec["a"], np.float64)[:, None, :]
    c = np.asarray(spec["c"], np.float64)
    over_c = (np.sum(y * m, axis=0) - c) / c
    over_a = (y - a) / a
    under = -y / a
    return float(max(over_c.max(), (over_a * m).max(), (under * m).max(), 0.0))


def run(config, traffic, *, seed, seconds, window, devices):
    import jax
    from repro.core.graph import ClusterSpec
    from repro.sched import job_manager

    window.mark("imports")
    rate = traffic["rate_hz"]
    warm = traffic["warmup_slots"]
    n = max(int(seconds * rate), 1)
    tc = dict(config, seed=int(drive.derived_seeds(seed, 1)[0]), T=warm + n)
    spec_np = reference.host_spec(tc, config["templates"])
    x = reference.host_arrivals(tc)
    spec = ClusterSpec(**{k: jax.device_put(v) for k, v in spec_np.items()})
    names = [f"port{l}" for l in range(tc["L"])]
    jobs = [job_manager.JobTemplate(arch=name, chips=float(spec_np["a"][l, 0]),
                                    hbm_gb=float(spec_np["a"][l, 1]))
            for l, name in enumerate(names)]
    jm = job_manager.JobManager(spec, jobs, eta0=config["oga"]["eta0"],
                                decay=config["oga"]["decay"])
    window.mark("inputs")
    grants = [jm.step(x[t]) for t in range(warm)]  # set-up: warms every op
    latency, late = np.zeros(n), np.zeros(n)
    t0 = window.open()
    for i in range(n):
        due = t0 + i / rate
        ahead = due - time.perf_counter()
        if ahead > 0:
            with window.span("wait"):
                time.sleep(ahead)
        start = time.perf_counter()
        with window.span("decision"):
            grants.append(jm.step(x[warm + i]))
        t_last = time.perf_counter()
        latency[i], late[i] = t_last - due, start - due
    window.close(t_last)
    held = {}

    def release():
        nonlocal jm
        held["y"] = np.asarray(jm.state.y)
        jm = None
        gc.collect()

    def check(control=False):
        eta0, decay = config["oga"]["eta0"], config["oga"]["decay"]
        _, y_ref, chips, _ = reference.oga_jit(spec_np, x, eta0, decay)
        y_ref, chips = np.asarray(y_ref), np.asarray(chips)
        if control:
            import jax.numpy as jnp
            _, y_c, chips_c, _ = reference.oga_jit(spec_np, x, eta0, decay,
                                                dtype=jnp.bfloat16)
            y = np.asarray(y_c.astype(jnp.float32))
            g = [{names[l]: int(v) for l, v in enumerate(row) if x[t, l] > 0}
                 for t, row in enumerate(reference.pow2_grant(
                     np.asarray(chips_c)))]
        else:
            y, g = held["y"], grants
        return {"y_gap": drive.rel_gap(y, y_ref,
                                       floor=float(np.max(np.abs(y_ref)))),
                "grant_mismatch": grant_mismatches(g, names, x, chips),
                "feasibility_excess": feasibility_excess(y, spec_np)}

    return drive.Run(
        end_to_end={"decision_ms_p50": float(np.percentile(latency, 50)) * 1e3,
                    "decision_ms_p95": float(np.percentile(latency, 95)) * 1e3},
        stats={"window_s": window.seconds, "decisions": n,
               "late_ms_p95": float(np.percentile(late, 95)) * 1e3,
               "late_ms_max": float(np.max(late)) * 1e3,
               "late_ms_last_tenth": float(np.mean(late[-max(n // 10, 1):]))
               * 1e3},
        attempted=n, failed=0, release=release, check=check)
