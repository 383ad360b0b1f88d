"""Distributed (shard_map) OGASCHED step == single-device step.

Runs in a subprocess so the 8-device host-platform flag does not leak into
the rest of the test session (jax pins device count at first init).
"""
import os
import pathlib
import subprocess
import sys
import textwrap

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_distributed_step_matches_single_device():
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import distributed, ogasched, reward, projection
        from repro.sched import trace

        assert jax.device_count() == 8
        cfg = trace.TraceConfig(L=6, R=32, K=4, seed=0)
        spec = trace.build_spec(cfg)
        mesh = jax.make_mesh((8,), ("data",))
        step = distributed.make_distributed_step(spec, mesh, axis="data")
        sspec = distributed.shard_spec(spec, mesh, axis="data")

        key = jax.random.PRNGKey(0)
        from repro.core import graph
        y = graph.random_feasible_decision(spec, key)
        x = (jax.random.uniform(jax.random.PRNGKey(1), (6,)) < 0.7).astype(jnp.float32)
        eta = jnp.asarray(3.0)

        with jax.set_mesh(mesh):
            y_next_d, q_d = step(sspec, y, x, eta)
        # single-device reference
        q_ref = reward.total_reward(spec, x, y)
        g = reward.reward_grad(spec, x, y)
        y_ref = projection.project(spec, y + eta * g)
        np.testing.assert_allclose(float(q_d), float(q_ref), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(y_next_d), np.asarray(y_ref), atol=2e-5
        )
        print("DISTRIBUTED-OK")
        """
    )
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": str(REPO / "src")},
        cwd=REPO,
        timeout=300,
    )
    assert "DISTRIBUTED-OK" in res.stdout, res.stdout + res.stderr
