"""Baseline heuristics: feasibility and expected qualitative behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, graph
from repro.sched import trace


@pytest.fixture(scope="module")
def setup():
    cfg = trace.TraceConfig(T=60, L=8, R=24, K=6, seed=2, contention=10.0)
    spec, arr = trace.make(cfg)
    return spec, arr


@pytest.mark.parametrize("name", baselines.BASELINES)
def test_feasible_allocations(setup, name):
    spec, arr = setup
    step = baselines._STEP_FNS[name]
    w = None if name == "fairness" else baselines._default_w(spec, name)
    for t in [0, 7, 31]:
        y = step(spec, arr[t], w) if w is not None else step(spec, arr[t])
        assert bool(graph.feasible(spec, y)), (name, t)


@pytest.mark.parametrize("name", baselines.BASELINES)
def test_no_allocation_to_empty_ports(setup, name):
    spec, arr = setup
    x = jnp.zeros(spec.L)
    step = baselines._STEP_FNS[name]
    y = step(spec, x)
    np.testing.assert_allclose(np.asarray(y), 0.0, atol=1e-7)


def test_fairness_shares_capacity_proportionally(setup):
    spec, _ = setup
    x = jnp.ones(spec.L)
    y = baselines.fairness_step(spec, x)
    used = jnp.sum(y, axis=0)  # (R, K)
    assert bool(jnp.all(used <= spec.c + 1e-4))


def test_binpacking_concentrates_vs_spreading():
    """Binpacking allocations should touch fewer (or equal) instances."""
    cfg = trace.TraceConfig(T=10, L=8, R=32, K=6, seed=5, contention=30.0)
    spec, arr = trace.make(cfg)
    x = arr[3]
    yb = baselines.binpacking_step(spec, x)
    ys = baselines.spreading_step(spec, x)
    nb = int(jnp.sum(jnp.any(jnp.sum(yb, 2) > 1e-6, axis=0)))
    ns = int(jnp.sum(jnp.any(jnp.sum(ys, 2) > 1e-6, axis=0)))
    assert nb <= ns, (nb, ns)


def test_registry_split():
    """Goldens key on the heuristic four; the optimal policies extend, not
    replace, them — and every name resolves to a step function."""
    assert baselines.BASELINES == ("drf", "fairness", "binpacking", "spreading")
    assert baselines.OPTIMAL_BASELINES == ("hesrpt", "multiclass")
    assert baselines.ALL_BASELINES == baselines.BASELINES + baselines.OPTIMAL_BASELINES
    assert set(baselines.SIZE_AWARE) <= set(baselines.ALL_BASELINES)
    for name in baselines.ALL_BASELINES:
        assert callable(baselines.step_fn(name))


@pytest.mark.parametrize("name", baselines.OPTIMAL_BASELINES)
def test_optimal_baselines_feasible(setup, name):
    spec, arr = setup
    sizes = jnp.where(arr[7] > 0, 10.0, 0.0)
    kw = {"sizes": sizes} if name in baselines.SIZE_AWARE else {}
    y = baselines.step_fn(name)(spec, arr[7], None, **kw)
    assert bool(graph.feasible(spec, y))
    off = np.asarray(arr[7]) == 0
    np.testing.assert_allclose(np.asarray(y)[off], 0.0, atol=1e-6)


def test_multiclass_dominates_heuristics_per_slot(setup):
    """The per-slot fluid argmax must out-reward every heuristic on the
    same slot — it is optimizing exactly that objective."""
    from repro.core import reward

    spec, arr = setup
    x = arr[7]
    fluid = float(reward.total_reward(
        spec, x, baselines.multiclass_step(spec, x)
    ))
    for name in baselines.BASELINES:
        w = baselines.default_parallelism(spec, name)
        y = baselines.step_fn(name)(spec, x, w)
        assert fluid >= float(reward.total_reward(spec, x, y)) - 1e-3, name


def test_size_aware_run_requires_works(setup):
    spec, arr = setup
    with pytest.raises(ValueError, match="size-aware"):
        baselines.run(spec, arr, "hesrpt")
    works = jnp.where(arr > 0, 12.0, 0.0)
    rewards = baselines.run(spec, arr, "hesrpt", works=works)
    assert rewards.shape == (arr.shape[0],)
    assert bool(jnp.all(jnp.isfinite(rewards)))


def test_default_parallelism_none_for_unbudgeted(setup):
    spec, _ = setup
    assert baselines.default_parallelism(spec, "fairness") is None
    for name in baselines.OPTIMAL_BASELINES:
        assert baselines.default_parallelism(spec, name) is None


def test_drf_orders_by_dominant_share():
    """Under extreme scarcity the lowest-dominant-share port wins resources."""
    L, R, K = 2, 1, 1
    spec = trace.build_spec(trace.TraceConfig(L=L, R=R, K=K, seed=0))
    # craft: port0 tiny request, port1 huge; capacity only fits port0 fully
    import dataclasses

    spec = dataclasses.replace(
        spec,
        mask=jnp.ones((L, R)),
        a=jnp.asarray([[1.0], [50.0]]),
        c=jnp.asarray([[10.0]]),
    )
    y = baselines.drf_step(spec, jnp.ones(L), w=jnp.asarray([1.0, 1.0]))
    got0, got1 = float(y[0, 0, 0]), float(y[1, 0, 0])
    assert got0 == pytest.approx(1.0, abs=1e-5)  # low share served first
    assert got1 <= 9.0 + 1e-4


# -------------------------------------------- budgeted fill against a sort

def _oracle_fill(spec, x, w, port_order, sign):
    """The budgeted fill by sort and cumsum, port by port, in numpy."""
    a, c, mask = (np.asarray(v, np.float32) for v in (spec.a, spec.c, spec.mask))
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    L, R = mask.shape
    y = np.zeros((L, R, a.shape[1]), np.float32)
    rem = c.copy()
    for l in port_order:
        used = np.maximum(c - rem, np.float32(0.0))  # rem <= c: a no-op
        util = np.mean(used / np.maximum(c, np.float32(1e-9)), axis=1)
        pref = np.float32(sign) * util - np.float32(1e-6) * np.arange(R, dtype=np.float32)
        pref = np.where(mask[l] > 0, pref, np.float32(-1e30))
        order = np.argsort(-pref, kind="stable")
        take = np.minimum(a[l], rem[order]) * mask[l][order][:, None]
        cum = np.cumsum(take, axis=0)
        allowed = np.clip(w[l] * a[l] - (cum - take), 0.0, take) * x[l]
        y[l, order] = allowed
        rem[order] -= allowed
    return y


def _arrived_first(x):
    return np.argsort(np.where(np.asarray(x) > 0, 0, 1), kind="stable")


def _oracle_step(name, spec, x):
    w = baselines.default_parallelism(spec, name)
    if name == "drf":
        cap = np.asarray(spec.mask, np.float64) @ np.asarray(spec.c, np.float64)
        s = np.max(np.asarray(spec.a) / np.maximum(cap, 1e-9), axis=1)
        order = np.argsort(np.where(np.asarray(x) > 0, s, 1e30), kind="stable")
        return _oracle_fill(spec, x, w, order, 0.0)
    sign = 1.0 if name == "binpacking" else -1.0
    return _oracle_fill(spec, x, w, _arrived_first(x), sign)


# the Tab. 2 shape over 12 slots, the Fig. 5 shape over a few
FILL_SHAPES = {
    "tab2": (trace.TraceConfig(T=12, seed=3), range(12)),
    "fig5": (trace.TraceConfig(T=4, L=100, R=1024, K=6, rho=0.95,
                               contention=5.0, beta_range=(0.01, 0.015),
                               seed=3), range(2)),
}


@pytest.fixture(scope="module", params=sorted(FILL_SHAPES))
def fill_case(request):
    cfg, slots = FILL_SHAPES[request.param]
    spec, arr = trace.make(cfg)
    return spec, [arr[t] for t in slots]


@pytest.mark.parametrize("name", ["drf", "binpacking", "spreading"])
def test_budgeted_fill_matches_sort_oracle(fill_case, name):
    """The precedence-mask fill gives the sort-and-cumsum fill's rewards,
    stays feasible and leaves non-arrived ports empty."""
    from repro.core import reward

    spec, xs = fill_case
    step = jax.jit(baselines.step_fn(name))
    w = baselines.default_parallelism(spec, name)
    got, want = [], []
    for x in xs:
        y = step(spec, x, w)
        assert bool(graph.feasible(spec, y)), name
        off = np.asarray(x) == 0
        assert np.all(np.asarray(y)[off] == 0.0), name
        got.append(float(reward.total_reward(spec, x, y)))
        want.append(float(reward.total_reward(
            spec, x, jnp.asarray(_oracle_step(name, spec, x)))))
    got, want = np.asarray(got), np.asarray(want)
    gap = np.max(np.abs(got - want)) / np.mean(np.abs(want))
    assert gap <= 1e-6, (name, gap)


@pytest.mark.parametrize("name,sign", [("binpacking", 1.0), ("spreading", -1.0)])
def test_index_order_equals_arrived_first_order(fill_case, name, sign):
    """A port that did not arrive takes nothing, so visiting every port in
    index order gives the arrived-first order's decision bit for bit."""
    spec, xs = fill_case
    w = baselines.default_parallelism(spec, name)
    fill = jax.jit(baselines._budgeted_fill, static_argnums=3)
    for x in xs:
        order = jnp.asarray(_arrived_first(x), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(fill(spec, x, w, sign)),
            np.asarray(fill(spec, x, w, sign, order)),
        )
