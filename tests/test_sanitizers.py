"""Runtime sanitizer layer: the dynamic counterpart of repro.analysis.lint.

The linter flags host-sync, aliasing, and impurity patterns *syntactically*;
the ``@pytest.mark.sanitized`` subset here proves the shipped core paths
actually run clean under jax's runtime guards (``transfer_guard("disallow")``
+ ``checking_leaks()``, applied by the conftest fixture), and the
``CompilationCounter`` tests pin the compile-once-per-(shape, backend)
property the benchmark recompile gates enforce in CI.

Inputs are staged onto the device at module scope — BEFORE any guard is
active — because under "disallow" even ``jax.random.PRNGKey(0)`` (a host
scalar lift) is an implicit transfer. That is the point of the layer: the
upload happens once at a named boundary, and the compute paths under test
must then run entirely device-resident, pulling results back only through
the explicit ``jax.device_get``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import graph, ogasched, projection, regret, reward
from repro.sched import sweep, trace


def _inputs(seed):
    cfg = trace.TraceConfig(L=4, R=6, K=3, T=12, seed=seed)
    return trace.build_spec(cfg), trace.build_arrivals(cfg), cfg


_STAGED = {seed: _inputs(seed) for seed in (0, 1, 2)}
_KEY = jax.random.PRNGKey(0)
_ETA = jnp.float32(5.0)
_DECAY = jnp.float32(0.999)
_Y0 = graph.random_feasible_decision(_STAGED[0][0], _KEY)
_X0 = (
    jax.random.uniform(jax.random.fold_in(_KEY, 1), (_STAGED[0][2].L,)) < 0.7
).astype(jnp.float32)


# ------------------------------------------------ transfer/leak-clean paths --
@pytest.mark.sanitized
def test_reward_grad_path_clean_under_guards():
    # jit-wrapped: under the guard the compute must run device-resident
    # end to end (op-by-op jax lifts python scalar constants, which the
    # guard rightly rejects — jit bakes them into the executable instead)
    spec, _, _ = _STAGED[0]
    q = jax.jit(reward.total_reward)(spec, _X0, _Y0)
    g = jax.jit(reward.reward_grad)(spec, _X0, _Y0)
    q, g = jax.device_get((q, g))  # explicit d2h: legal under the guard
    assert np.isfinite(q)
    assert np.isfinite(g).all()


@pytest.mark.sanitized
def test_projection_path_clean_under_guards():
    spec, _, _ = _STAGED[0]

    @jax.jit
    def fill(spec):
        z = spec.a[:, None, :] * spec.mask[:, :, None]  # (L, R, K) demand
        L = z.shape[0]
        return projection.fill_rows_to_capacity(
            z.reshape(L, -1),
            jnp.broadcast_to(spec.a[:, None, :], z.shape).reshape(L, -1),
            jnp.broadcast_to(spec.mask[:, :, None], z.shape).reshape(L, -1),
            jnp.sum(spec.c) * jnp.ones((L,)) * 0.1,
        )

    y = jax.device_get(fill(spec))
    assert np.isfinite(y).all()
    assert (y >= -1e-6).all()


@pytest.mark.sanitized
def test_oga_run_clean_under_guards():
    spec, arrivals, cfg = _STAGED[1]
    rewards, y_final = ogasched.run(spec, arrivals, eta0=_ETA, decay=_DECAY)
    rewards = jax.device_get(rewards)
    assert rewards.shape == (cfg.T,)
    assert np.isfinite(rewards).all()
    assert bool(jax.device_get(jax.jit(graph.feasible)(spec, y_final)))


@pytest.mark.sanitized
def test_regret_curve_path_clean_under_guards():
    spec, arrivals, cfg = _STAGED[2]
    rewards, _ = ogasched.run(spec, arrivals, eta0=_ETA, decay=_DECAY)
    y_star = jax.jit(lambda s, a: regret.offline_optimum(s, a, iters=16))(
        spec, arrivals
    )
    curve = jax.device_get(
        jax.jit(regret.regret_curve)(spec, arrivals, rewards, y_star)
    )
    assert curve.shape == (cfg.T,)
    assert np.isfinite(curve).all()


# ------------------------------------------------------ compilation counter --
def test_compilation_counter_counts_fresh_compiles():
    @jax.jit
    def f(x):
        return x * 2.0 + 1.0

    x = jnp.arange(13, dtype=jnp.float32)
    with compat.CompilationCounter() as c1:
        jax.block_until_ready(f(x))
    with compat.CompilationCounter() as c2:
        jax.block_until_ready(f(x))
    assert c1.count >= 1  # cold call really compiled
    assert c2.count == 0  # warm call hit the jit cache


def _drain(points, **kw):
    for _, _, out in sweep.run_grid_stream(points, ("ogasched",), **kw):
        jax.block_until_ready(out)


def test_sweep_stream_compiles_once_per_chunk_shape(compile_counter):
    """After chunk 0 compiles, every same-shape chunk must be a cache hit
    — the property the bench-sweep recompile gate enforces in CI."""
    base = trace.TraceConfig(L=4, R=6, K=3, T=10)
    pts = sweep.make_grid(base, eta0s=(5.0, 10.0), seeds=(0, 1))  # G=4
    kw = dict(chunk_size=2, trace_backend="host")
    it = sweep.run_grid_stream(pts, ("ogasched",), **kw)
    _, _, out = next(it)  # chunk 0: pays all compilation
    jax.block_until_ready(out)
    with compile_counter() as c:
        for _, _, out in it:
            jax.block_until_ready(out)
    assert c.count == 0


def test_sweep_stream_warm_rerun_compiles_nothing(compile_counter):
    base = trace.TraceConfig(L=4, R=6, K=3, T=10)
    pts = sweep.make_grid(base, eta0s=(5.0, 10.0), seeds=(0, 1))
    kw = dict(chunk_size=2, trace_backend="host")
    _drain(pts, **kw)  # warm
    with compile_counter() as c:
        _drain(pts, **kw)
    assert c.count == 0


def test_regret_stream_compiles_once_per_chunk_shape(compile_counter):
    base = trace.TraceConfig(L=4, R=6, K=3, T=16)
    pts = sweep.make_grid(base, eta0s=(5.0,), seeds=(0, 1, 2, 3))
    kw = dict(chunk_size=2, oracle_iters=8, trace_backend="host")
    regret.regret_stream(pts, **kw)  # warm: compiles for the (2, T) chunk
    with compile_counter() as c:
        out = regret.regret_stream(pts, **kw)
    assert c.count == 0
    assert out["curves"].shape == (4, out["ts"].size)
