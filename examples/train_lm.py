"""End-to-end driver: train a stablelm-family LM for a few hundred steps with
checkpoint/restart and straggler flags.

Default is a CPU-feasible ~10M config (CI-speed); ``--full-100m`` selects the
~100M layout (8L x d512 x 50304 vocab) intended for accelerator hosts.

    PYTHONPATH=src python examples/train_lm.py [--steps 60] [--full-100m]
"""
import argparse
import dataclasses

from repro import compat
from repro.configs import base as configs
from repro.data.pipeline import DataConfig
from repro.optim import AdamWConfig
from repro.train.trainer import TrainConfig, Trainer

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=60)
ap.add_argument("--full-100m", action="store_true")
ap.add_argument("--ckpt-dir", default="/tmp/repro_example_ckpt")
args = ap.parse_args()
compat.use_repo_compile_cache()

if args.full_100m:
    # ~100M params: 51M tied-scale embeddings + 8 x 3.1M blocks + head
    cfg = dataclasses.replace(
        configs.reduced(configs.get("stablelm-3b")),
        n_layers=8, d_model=512, n_heads=8, n_kv=8, head_dim=64, d_ff=1408,
        vocab=50304,
    )
    batch, seq = 8, 256
else:
    cfg = dataclasses.replace(
        configs.reduced(configs.get("stablelm-3b")),
        n_layers=6, d_model=256, n_heads=8, n_kv=8, head_dim=32, d_ff=704,
        vocab=8192,
    )
    batch, seq = 4, 128
opt = AdamWConfig(lr=3e-3, warmup_steps=30, total_steps=args.steps)
data = DataConfig(vocab=cfg.vocab, global_batch=batch, seq_len=seq)
tc = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=100)

out = Trainer(cfg, opt, data, tc).run(
    hooks={
        "on_step": lambda s, l, dt, slow: (
            print(f"step {s:4d} loss {l:.4f} {dt*1e3:6.0f}ms")
            if s % 20 == 0
            else None
        )
    }
)
print(f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")
assert out["losses"][-1] < out["losses"][0], "training must reduce loss"
