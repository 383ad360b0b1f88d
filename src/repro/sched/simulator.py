"""Trace-driven cluster simulator (paper §4) + algorithm comparison API."""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import baselines, graph, ogasched, regret
from repro.sched import lifecycle, sweep, trace


@dataclasses.dataclass
class SimResult:
    name: str
    rewards: np.ndarray           # (T,)
    avg_reward: float
    cumulative: float
    wall_s: float
    regret: Optional[float] = None
    regret_bound: Optional[float] = None
    # lifecycle-mode metrics (lifecycle.summarize): jct_mean, jct_p99,
    # slowdown_mean, utilization[/k], completed, dropped, throughput.
    lifecycle: Optional[dict] = None


def run_all(
    cfg: trace.TraceConfig,
    eta0: float = 25.0,
    decay: float = 0.9999,
    algorithms: tuple = ("ogasched",) + baselines.BASELINES,
    with_regret: bool = False,
    oracle_iters: int = 2000,
    backend: str = "auto",
    mode: str = "slot",
    queue_depth: int = 8,
    rate_floor: float = 1e-3,
    fault_policy: lifecycle.FaultPolicy = lifecycle.FaultPolicy(),
) -> dict[str, SimResult]:
    """Single-configuration comparison; each algorithm goes through the same
    paths the vectorised grid uses (``sweep.run_algorithm`` /
    ``lifecycle.run``), so run_all on one config and run_grid on G configs
    agree by construction.

    mode="lifecycle" runs the occupancy-aware job lifecycle (jobs hold
    their allocation until their work drains; sched.lifecycle) and fills
    ``SimResult.lifecycle`` with JCT/slowdown/utilization metrics. An
    active ``cfg.faults`` process additionally injects the capacity-fault
    stream (trace.build_faults) with ``fault_policy`` eviction/retry
    semantics — lifecycle mode only (slot mode raises, matching the sweep
    engine). Regret is a slot-mode notion (the comparator plays every slot
    from full capacity), so ``with_regret`` only applies in slot mode.
    """
    if mode not in ("slot", "lifecycle"):
        raise ValueError(f"mode must be 'slot' or 'lifecycle', got {mode!r}")
    # reuse the sweep engine's gate: active fault configs in slot mode are
    # a config error, not something to silently ignore
    has_faults = sweep.needs_faults([sweep.SweepPoint(cfg=cfg)], mode)
    with obs.span("run_all.synthesis"):
        spec, arrivals = trace.make(cfg)
        works = (
            trace.build_works(cfg)
            if sweep.needs_works(algorithms, mode) else None
        )
        faults = trace.build_faults(cfg) if has_faults else None
    out: dict[str, SimResult] = {}
    y_star = None
    # The oracle only feeds OGASCHED's regret certificate — skip the
    # oracle_iters-step offline solve when nothing will consume it.
    if with_regret and mode == "slot" and "ogasched" in algorithms:
        y_star = regret.offline_optimum(spec, arrivals, iters=oracle_iters)
    for name in algorithms:
        t0 = time.time()
        metrics = None
        if mode == "lifecycle":
            tr = lifecycle.run(
                spec, arrivals, works, name,
                eta0=eta0, decay=decay, backend=backend,
                queue_depth=queue_depth, rate_floor=rate_floor,
                faults=faults, fault_policy=fault_policy,
            )
            tr = jax.block_until_ready(tr)
            rewards = np.asarray(tr.rewards)
            # the jitted batched reduction on a single-row "grid" — the same
            # code path sweep.summarize_lifecycle runs over whole grids
            batched = lifecycle.summarize_batch(
                jax.tree.map(lambda l: l[None], tr),
                jax.tree.map(lambda l: l[None], spec),
            )
            metrics = {k: float(v[0]) for k, v in batched.items()}
        else:
            with obs.span(f"run_all.{name}"):
                rewards = sweep.run_algorithm(
                    spec, arrivals, name, eta0=eta0, decay=decay,
                    backend=backend,
                    works=works if name in baselines.SIZE_AWARE else None,
                )
                with obs.span("run_all.wait"):
                    rewards = np.asarray(jax.block_until_ready(rewards))
        res = SimResult(
            name=name,
            rewards=rewards,
            avg_reward=float(rewards.mean()),
            cumulative=float(rewards.sum()),
            wall_s=time.time() - t0,
            lifecycle=metrics,
        )
        if y_star is not None and name == "ogasched":
            res.regret = float(
                regret.regret(spec, arrivals, jnp.asarray(rewards), y_star)
            )
            res.regret_bound = float(regret.regret_bound(spec, cfg.T))
        out[name] = res
    return out


def improvement_over_baselines(results: dict[str, SimResult]) -> dict[str, float]:
    """OGASCHED's percentage improvement per baseline, signed-safe
    (sweep.improvement_pct): finite at zero-reward baselines and
    sign-correct at negative ones."""
    oga = results["ogasched"].avg_reward
    return {
        n: float(sweep.improvement_pct(oga, r.avg_reward))
        for n, r in results.items()
        if n != "ogasched"
    }
