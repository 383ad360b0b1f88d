"""Expert-parallel MoE (shard_map) == single-device reference (subprocess —
needs 8 host devices before jax initialises)."""
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_moe_ep_matches_reference():
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ArchConfig
        from repro.models import moe as moe_lib
        from repro.train.meshctx import use_mesh

        cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=32,
                         n_heads=4, n_kv=2, d_ff=0, vocab=64, n_experts=8,
                         top_k=2, d_expert=16, n_shared_experts=1,
                         capacity_factor=8.0, param_dtype="float32",
                         compute_dtype="float32")
        p = moe_lib.init_moe(jax.random.PRNGKey(0), 32, 16, 8, 1, jnp.float32)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)

        # full-seq path (all_gather + psum_scatter)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        ref = moe_lib.apply_moe(p, x.reshape(-1, 32), 2, 8.0).reshape(4, 16, 32)
        got = jax.jit(lambda pp, xx: moe_lib.apply_moe_ep(pp, xx, cfg, mesh))(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

        # decode path (psum fallback, S=1)
        x1 = jax.random.normal(jax.random.PRNGKey(2), (8, 1, 32))
        ref1 = moe_lib.apply_moe(p, x1.reshape(-1, 32), 2, 8.0).reshape(8, 1, 32)
        got1 = jax.jit(lambda pp, xx: moe_lib.apply_moe_ep(pp, xx, cfg, mesh))(p, x1)
        np.testing.assert_allclose(np.asarray(got1), np.asarray(ref1), atol=1e-5)

        # gradient path finite
        g = jax.grad(lambda pp: jnp.sum(
            moe_lib.apply_moe_ep(pp, x, cfg, mesh) ** 2))(p)
        assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(g))
        print("MOE-EP-OK")
        """
    )
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=REPO, timeout=600,
    )
    assert "MOE-EP-OK" in res.stdout, res.stdout + res.stderr
