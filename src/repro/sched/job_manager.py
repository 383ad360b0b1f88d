"""OGASCHED -> mesh-slice job manager (the paper's technique as the
framework's cluster scheduler; DESIGN.md §2).

Ports = LM training/serving job types (the 10 assigned archs), instances =
TPU hosts/slices, K resources = [chips, HBM GB, ICI links, host CPU, host
DRAM, NIC]. OGASCHED's fractional allocation y is converted into discrete
device grants per job; grants drive elastic data-axis scaling between
checkpoint boundaries (launch/elastic.py performs the resharding).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import ogasched
from repro.core.graph import ClusterSpec
from repro.sched import trace

# resource vector indices for LM jobs
RES = ("chips", "hbm_gb", "ici_links", "host_cpu", "host_dram_gb", "nic_gbps")


@dataclasses.dataclass
class JobTemplate:
    arch: str
    # per-channel (per-instance) max request a_l^k
    chips: float
    hbm_gb: float
    ici: float = 4.0
    cpu: float = 8.0
    dram: float = 32.0
    nic: float = 25.0

    def vector(self) -> np.ndarray:
        return np.array(
            [self.chips, self.hbm_gb, self.ici, self.cpu, self.dram, self.nic]
        )


def templates_from_dryrun(records: dict) -> list[JobTemplate]:
    """Derive job resource vectors from dry-run memory analysis: HBM demand
    = per-device args+temps; chips request = per-instance slice of the mesh."""
    out = []
    for arch, rec in records.items():
        mem = rec.get("memory", {})
        hbm = (
            mem.get("argument_size_in_bytes", 0)
            + mem.get("temp_size_in_bytes", 0)
        ) / 1e9
        out.append(JobTemplate(arch=arch, chips=4.0, hbm_gb=min(hbm, 64.0)))
    return out


def build_cluster(
    jobs: list[JobTemplate], n_hosts: int = 128, seed: int = 0
) -> ClusterSpec:
    """Bipartite spec: hosts with 4 chips / 64GB HBM / ICI / CPU / DRAM.

    Randomness comes from the repo-wide SeedSequence stream discipline
    (trace.stream_rng, stream "cluster"), NOT a raw default_rng(seed):
    raw seeding made build_cluster(seed=s) share bits with any other
    component seeded s — the exact collision class the trace streams were
    split to kill (tests/test_trace.py).
    """
    rng = trace.stream_rng(seed, "cluster")
    L, K = len(jobs), len(RES)
    cap = np.array([4.0, 64.0, 16.0, 96.0, 256.0, 100.0])
    c = cap[None, :] * rng.uniform(0.9, 1.1, (n_hosts, K))
    a = np.stack([j.vector() for j in jobs])
    mask = (rng.uniform(size=(L, n_hosts)) < 0.6).astype(np.float32)
    mask[:, 0] = 1.0  # every job can reach host 0
    alpha = rng.uniform(1.0, 1.5, (n_hosts, K))
    beta = np.linspace(0.3, 0.5, K)
    kinds = np.array([1, 3, 2, 1, 3, 2])  # log/poly/recip mix: concave gains
    return ClusterSpec(
        mask=jnp.asarray(mask),
        a=jnp.asarray(a, jnp.float32),
        c=jnp.asarray(c, jnp.float32),
        alpha=jnp.asarray(alpha, jnp.float32),
        beta=jnp.asarray(beta, jnp.float32),
        kinds=jnp.asarray(kinds, jnp.int32),
    )


class JobManager:
    """Runs OGASCHED online over job arrivals; exposes integral chip grants."""

    def __init__(self, spec: ClusterSpec, jobs: list[JobTemplate], eta0=25.0,
                 decay=0.9999):
        self.spec = spec
        self.jobs = jobs
        self.state = ogasched.init_state(spec, eta0)
        self.decay = decay

    def step(self, arrivals: jnp.ndarray) -> dict[str, int]:
        """One slot: returns integral chips granted per arrived job."""
        with obs.span("online.dispatch"):
            self.state, _ = ogasched.oga_step(
                self.spec, self.state, arrivals, self.decay
            )
        with obs.span("online.to_host"):
            y = np.asarray(self.state.y)  # (L, R, K)
        with obs.span("online.grants"):
            chips = y[:, :, 0].sum(axis=1)  # total chips across hosts
            grants = {}
            for l, job in enumerate(self.jobs):
                if float(arrivals[l]) > 0:
                    # round to power-of-two data-axis sizes (mesh-sliceable)
                    g = int(chips[l])
                    grants[job.arch] = (
                        1 << max(g.bit_length() - 1, 0) if g > 0 else 0
                    )
        return grants
