"""GPipe pipeline-parallel forward == scanned reference (subprocess, 4-stage
pipeline on 4 host devices), gradients included."""
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_pipeline_matches_reference_and_grads():
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import base as configs
        from repro.models import model as M, pipeline as PP, transformer as tf

        cfg = configs.reduced(configs.get("stablelm-3b"), n_layers=8)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        mesh = jax.make_mesh((4,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        B, S = 4, 16
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        ref = tf.stack_forward(params["blocks"], cfg, x, positions)
        got = jax.jit(lambda p, xx: PP.pipeline_forward(
            p, cfg, xx, positions, mesh, n_micro=2))(params["blocks"], x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4)

        # gradients flow through the permute chain (GPipe backward)
        g = jax.grad(lambda p: jnp.sum(PP.pipeline_forward(
            p, cfg, x, positions, mesh, n_micro=2) ** 2))(params["blocks"])
        gr = jax.grad(lambda p: jnp.sum(tf.stack_forward(
            p, cfg, x, positions) ** 2))(params["blocks"])
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-3, rtol=5e-3)
        print("PIPELINE-OK")
        """
    )
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=REPO, timeout=900,
    )
    assert "PIPELINE-OK" in res.stdout, res.stdout + res.stderr[-3000:]
