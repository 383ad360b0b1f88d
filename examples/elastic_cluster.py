"""Scheduler-driven elastic training: OGASCHED (the paper's algorithm) grants
chips to competing LM jobs online; the job manager converts grants into mesh
sizes and the trainer reshards at checkpoint boundaries.

    PYTHONPATH=src python examples/elastic_cluster.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro import compat
from repro.launch.elastic import plan_mesh
from repro.sched.job_manager import JobManager, JobTemplate, build_cluster

compat.use_repo_compile_cache()

jobs = [
    JobTemplate(arch="qwen2-72b", chips=4.0, hbm_gb=48.0),
    JobTemplate(arch="kimi-k2-1t-a32b", chips=4.0, hbm_gb=64.0),
    JobTemplate(arch="mamba2-780m", chips=2.0, hbm_gb=8.0),
    JobTemplate(arch="stablelm-3b", chips=2.0, hbm_gb=16.0),
]
spec = build_cluster(jobs, n_hosts=64, seed=0)
mgr = JobManager(spec, jobs)

rng = np.random.default_rng(0)
history = {j.arch: [] for j in jobs}
for t in range(40):
    arrivals = jnp.asarray((rng.uniform(size=len(jobs)) < 0.7).astype(np.float32))
    grants = mgr.step(arrivals)
    for arch, chips in grants.items():
        history[arch].append(chips)
        if t % 10 == 0 and chips:
            dp, tp = plan_mesh(chips)
            print(f"t={t:3d} {arch:18s} -> {chips:4d} chips  mesh=({dp},{tp})")

print("\nmean granted chips (scheduler learned the gain-overhead tradeoff):")
for arch, h in history.items():
    if h:
        print(f"  {arch:18s} {np.mean(h):8.1f}")
