"""Elastic rescale: checkpoint on mesh A -> restore on mesh B (subprocess
with 8 host devices), values bit-identical; plan_mesh power-of-two logic."""
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.launch.elastic import plan_mesh

REPO = Path(__file__).resolve().parent.parent


def test_plan_mesh_power_of_two():
    assert plan_mesh(64) == (4, 16)
    assert plan_mesh(16) == (1, 16)
    assert plan_mesh(100) == (4, 16)  # rounds down to 64
    assert plan_mesh(8) == (1, 8)


def test_reshard_across_meshes():
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.ckpt import checkpoint as C
        from repro.launch.elastic import rescale_checkpoint, reshard
        from repro.train import sharding as shd

        tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones(8)}
        mesh_a = jax.make_mesh((8, 1), ("data", "model"))
        mesh_b = jax.make_mesh((2, 4), ("data", "model"))

        placed = reshard(tree, mesh_a)
        d = tempfile.mkdtemp()
        C.save_checkpoint(d, placed, 7)
        out = rescale_checkpoint(d, 7, tree, mesh_b)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
        # placement really is on mesh_b
        assert out["w"].sharding.mesh.shape["model"] == 4
        print("ELASTIC-OK")
        """
    )
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=REPO, timeout=600,
    )
    assert "ELASTIC-OK" in res.stdout, res.stdout + res.stderr
