"""Serve a small model with batched requests (continuous batching engine).

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax

from repro import compat
from repro.configs import base as configs
from repro.models import model as M
from repro.serve.engine import Engine, Request

compat.use_repo_compile_cache()

cfg = configs.reduced(configs.get("musicgen-medium"))
params = M.init_params(cfg, jax.random.PRNGKey(0))
eng = Engine(cfg, params, slots=4, cache_len=64, temperature=0.7, seed=1)

reqs = [Request(prompt=[10 * i + 1, 10 * i + 2], max_new_tokens=16) for i in range(8)]
for r in reqs:
    eng.submit(r)
t0 = time.time()
eng.run()
dt = time.time() - t0
assert all(r.done for r in reqs)
total = sum(len(r.out) for r in reqs)
print(f"decoded {total} tokens across {len(reqs)} requests in {dt:.2f}s "
      f"({total/dt:.1f} tok/s, {eng.steps_run} batched engine steps)")
for i, r in enumerate(reqs[:3]):
    print(f"req{i}: {r.prompt} -> {r.out}")
