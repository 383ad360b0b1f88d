"""The names the program gives its own work in a profiler trace.

``scope(name)`` names traced code: ``jax.named_scope("repro." + name)``. The
name is metadata at trace time; it reaches each HLO op's ``op_name`` (as
``.../repro.<name>/...``, or ``vmap(repro.<name>)`` under a transform), so
it costs nothing on the device and nothing when no profiler runs.

``span(name)`` names a host phase: ``jax.profiler.TraceAnnotation("repro." +
name)``. It lands in whatever profiler session is running, on the same
clock as the device ops; with none running it costs one native call.

Whoever starts the profiler gets both; there is no option to turn them on.
``NAMES`` lists every scope and span and what reads it. ``<algorithm>`` and
``<name>`` stand for an algorithm's or heuristic's name.
"""
from __future__ import annotations

import jax

PREFIX = "repro."

NAMES = {
    # device scopes: chipbench/scopes.py reads each as a share of busy time
    "reward": "reward.total_reward, the reward every algorithm evaluates "
              "each slot: reward_busy_share",
    "heuristic.<name>": "one heuristic's allocation step in baselines.run "
                        "and in the lifecycle step, its reward left out: "
                        "heuristic_busy_share.<name>",
    "oga.update": "OGASched's update in ogasched.oga_step, run_batch and "
                  "the lifecycle step (packing, k*, the fused kernel, "
                  "unpacking): oga_update_busy_share",
    "lifecycle.evict": "the lifecycle step's fault multiplier and eviction "
                       "(lifecycle._evict): scopes.py, by name",
    "lifecycle.queue": "the lifecycle step's enqueue, admission and queue "
                       "shifts: scopes.py, by name",
    "lifecycle.allocate": "the lifecycle step's allocation: residual "
                          "capacity, the heuristic's proposal, the exact "
                          "projection, service rates: scopes.py, by name",
    "lifecycle.service": "the lifecycle step's drain and departures: "
                         "scopes.py, by name",
    # host spans: chipbench/scopes.py labels idle gaps by them and sums them
    "run_all.synthesis": "simulator.run_all's trace synthesis",
    "run_all.<algorithm>": "one algorithm's dispatch and wait in "
                           "simulator.run_all, in slot mode",
    "run_all.wait": "run_all's wait for an algorithm's rewards and their "
                    "copy to the host",
    "sweep.wait": "run_grid_stream's wait for the next chunk, the time "
                  "its stats count as chunk_wait_s",
    "sweep.dispatch": "run_grid_stream's dispatch of a chunk's programs",
    "sweep.synthesis": "the prefetch worker's build_batch of one chunk "
                       "(synthesis and upload), on the worker's thread",
    "sweep.summarize": "sweep.summarize's copy of a chunk's rewards to the "
                       "host and their reduction there; in lifecycle mode, "
                       "summarize_lifecycle's reduction and copy",
    "online.dispatch": "JobManager.step's OGA step, dispatched op by op",
    "online.to_host": "JobManager.step's wait for y and its copy to the host",
    "online.grants": "JobManager.step's rounding of y to grants",
}


def scope(name: str):
    """Name the ops traced inside: ``repro.<name>`` in their ``op_name``."""
    return jax.named_scope(PREFIX + name)


def span(name: str):
    """Name a host phase ``repro.<name>`` in a running profiler's trace."""
    return jax.profiler.TraceAnnotation(PREFIX + name)
