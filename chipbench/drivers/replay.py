"""The single-deployment comparison of every algorithm, ``simulator.run_all``
as the Fig. 5 reproduction runs it, on a new trace seed per call.

Traffic keys: ``max_calls``, the most calls a window can start (seeds are
drawn for that many); ``check_scenarios``, how many of the window's
scenarios, drawn from the seed, the check compares with the reference.
"""
from __future__ import annotations

import time

import numpy as np

import drive
import reference


def run(config, traffic, *, seed, seconds, window, devices):
    from repro.sched import simulator

    window.mark("imports")
    T = config["T"]
    oga = config["oga"]
    seeds = drive.derived_seeds(seed, traffic["max_calls"])

    def call(i):
        res = simulator.run_all(drive.trace_config(config, seed=int(seeds[i])),
                                eta0=oga["eta0"], decay=oga["decay"])
        gain = simulator.improvement_over_baselines(res)
        return ({n: r.rewards for n, r in res.items()},
                {n: r.avg_reward for n, r in res.items()}, gain)

    window.mark("inputs")
    call(0)  # set-up: compiles or loads every algorithm's program at this shape
    done = []
    window.open()
    while window.elapsed() < seconds:
        with window.span("call"):
            done.append(call(len(done) + 1))
        t_last = time.perf_counter()
    window.close(t_last)
    failed = sum(not all(np.isfinite(r).all() for r in rewards.values())
                 for rewards, _, _ in done)
    picks = sorted(drive.sample_rng(seed).choice(
        len(done), size=min(traffic["check_scenarios"], len(done)),
        replace=False))

    def check(control=False):
        tcs = [dict(config, seed=int(seeds[i + 1])) for i in picks]
        specs = [reference.host_spec(tc, config["templates"]) for tc in tcs]
        spec = {k: np.stack([s[k] for s in specs]) for k in specs[0]}
        x = np.stack([reference.host_arrivals(tc) for tc in tcs])
        eta0 = [oga["eta0"]] * len(tcs)
        decay = [oga["decay"]] * len(tcs)
        ref, untied = drive.reference_rewards(spec, x, eta0, decay,
                                              control=False)
        if control:
            return drive.control_gaps(spec, x, eta0, decay, ref, untied, info)
        prog = {n: np.stack([done[i][0][n] for i in picks])
                for n in drive.ALGORITHMS}
        avg = {n: np.asarray([done[i][1][n] for i in picks])
               for n in drive.ALGORITHMS}
        gain = {n: np.asarray([done[i][2][n] for i in picks])
                for n in reference.HEURISTICS}
        return drive.reward_gaps(prog, avg, gain, ref, untied, info)

    L, R, K = (config[k] for k in ("L", "R", "K"))
    info = {}
    return drive.Run(
        end_to_end={"scenario_slots_per_s": len(done) * T / window.seconds},
        stats={"window_s": window.seconds, "calls": len(done),
               "configs": len(done), "oga_decisions": len(done) * T,
               "oga_shape": (L, R, K)},
        attempted=len(done), failed=failed, check=check, info=info)
