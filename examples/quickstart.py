"""Quickstart: OGASCHED vs the four heuristics on a synthetic Alibaba-like
trace (paper Fig. 2 in miniature), plus the regret certificate.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro import compat
from repro.sched import trace
from repro.sched.simulator import improvement_over_baselines, run_all

compat.use_repo_compile_cache()

cfg = trace.TraceConfig(T=800, L=10, R=64, K=6, seed=1, contention=10.0)
results = run_all(cfg, with_regret=True)

print(f"{'algorithm':12s} {'avg reward':>12s} {'cumulative':>14s} {'wall':>7s}")
for name, r in results.items():
    print(f"{name:12s} {r.avg_reward:12.2f} {r.cumulative:14.1f} {r.wall_s:6.1f}s")

print("\nOGASCHED improvement over baselines (paper: DRF +11.33%, "
      "FAIRNESS +7.75%, BINPACKING +13.89%, SPREADING +13.44%):")
for name, pct in improvement_over_baselines(results).items():
    print(f"  vs {name:12s} +{pct:.2f}%")

oga = results["ogasched"]
print(f"\nregret R_T = {oga.regret:.1f}  <=  H_G*sqrt(T) = {oga.regret_bound:.1f} "
      f"({'OK' if oga.regret <= oga.regret_bound else 'VIOLATION'})")

# --- scenario sweep: a hyperparameter grid as ONE vmapped computation ------
# (docs/sweeps.md; sweep.run_grid matches looping run_all per config.)
from repro.sched import sweep

points = sweep.make_grid(cfg, eta0s=(10.0, 25.0), decays=(0.999, 0.9999))
batch = sweep.build_batch(points)
summary = sweep.summarize(sweep.run_grid(batch, algorithms=("ogasched", "fairness")))
print(f"\nsweep over {batch.size} configs (eta0 x decay):")
for p, avg, imp in zip(points, summary["avg/ogasched"],
                       summary["improvement_pct/fairness"]):
    print(f"  eta0={p.eta0:5.1f} decay={p.decay:6.4f}  "
          f"avg_reward={avg:8.2f}  vs fairness {imp:+.2f}%")

# Big grids stream in chunks instead (same numbers, O(chunk) memory, and
# the grid axis shards over a device mesh when one is available). Chunk
# traces for large grids are synthesized ON-DEVICE (trace_backend="auto")
# and prefetched on a background thread, so the stream is compute-bound:
#   points = sweep.make_grid(cfg, seeds=range(10_000))
#   summary = sweep.sweep_stream(points, chunk_size=256, sharded=True)

# --- resumable sweep: a streamed grid that survives kill -9 ---------------
# (docs/sweeps.md "Resumable sweeps". checkpoint_dir commits each chunk's
# summary crash-safely; rerunning the same call resumes from the finished
# prefix — here the second call recomputes nothing and returns identical
# summaries. The store refuses a different grid: SweepResumeMismatch.)
import tempfile

with tempfile.TemporaryDirectory() as ckpt_dir:
    first = sweep.sweep_stream(
        points, algorithms=("ogasched", "fairness"), chunk_size=2,
        checkpoint_dir=ckpt_dir,
    )
    resumed = sweep.sweep_stream(       # pure load: all chunks checkpointed
        points, algorithms=("ogasched", "fairness"), chunk_size=2,
        checkpoint_dir=ckpt_dir,
    )
assert all((resumed[k] == first[k]).all() for k in first)
print(f"\nresumable sweep: {len(points)} configs checkpointed + resumed "
      "bitwise-equal")

# --- job lifecycle: jobs hold resources, depart, and report JCT -----------
# (docs/lifecycle.md; mode="lifecycle" nets capacities by held allocations.)
import dataclasses

life_cfg = dataclasses.replace(cfg, work_mean=600.0)  # multi-slot jobs
life = run_all(life_cfg, mode="lifecycle", algorithms=("ogasched", "fairness"))
print("\nlifecycle mode (jobs hold resources until their work drains):")
for name, r in life.items():
    m = r.lifecycle
    print(f"  {name:12s} jct={m['jct_mean']:.2f} (p99 {m['jct_p99']:.1f}) "
          f"slowdown={m['slowdown_mean']:.2f} util={m['utilization']:.3f} "
          f"completed={m['completed']:.0f}")

# --- fault injection: failures, evictions, retry/backoff ------------------
# (docs/lifecycle.md "Faults, evictions, and retries". cfg.faults seeds a
# (T, K) capacity-multiplier stream; capacity drops evict marginal jobs,
# which retry with capped exponential backoff under lifecycle.FaultPolicy.
# A fault-free config still runs the pre-fault program bitwise.)
from repro.sched import lifecycle

fault_cfg = dataclasses.replace(
    life_cfg,
    faults=trace.FaultConfig(fail_rate=0.02, fail_frac=0.3, repair_mean=40.0),
)
faulted = run_all(
    fault_cfg, mode="lifecycle", algorithms=("ogasched", "fairness"),
    fault_policy=lifecycle.FaultPolicy(max_retries=3, preserve_work=True),
)
print("\nfault-injected lifecycle (server failures, exponential repair):")
for name, r in faulted.items():
    m = r.lifecycle
    clean = life[name].lifecycle
    print(f"  {name:12s} goodput={m['goodput']:.1f} "
          f"(clean {clean['goodput']:.1f}) wasted={m['wasted_work']:.0f} "
          f"evictions={m['evictions']:.0f} drops={m['fault_drops']:.0f}")
