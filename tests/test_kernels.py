"""Pallas kernels vs pure-jnp/numpy oracles (interpret=True on CPU).

Per the brief: shape/dtype sweeps + assert_allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dependency-free fallback (see _hypothesis_compat)
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import autotune, ops, ref, sortscan
from repro.kernels.flash_attention import flash_attention
from repro.kernels.oga_step import oga_step_fused
from repro.kernels.proj_bisect import proj_bisect
from repro.kernels.sortscan import proj_sortscan


# ------------------------------------------------------------ projection ---
@pytest.mark.parametrize("N,L", [(4, 8), (16, 24), (33, 130), (8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_proj_bisect_shapes(N, L, dtype):
    key = jax.random.fold_in(jax.random.PRNGKey(N), L)
    kz, ka, km, kc = jax.random.split(key, 4)
    z = (jax.random.normal(kz, (N, L)) * 5).astype(dtype)
    a = jax.random.uniform(ka, (N, L), minval=0.1, maxval=4.0).astype(dtype)
    mask = (jax.random.uniform(km, (N, L)) < 0.8).astype(dtype)
    c = jax.random.uniform(kc, (N,), minval=0.3, maxval=6.0).astype(dtype)
    got = proj_bisect(z, a, mask, c, interpret=True)
    want = ref.proj_rows_exact_np(z, a, mask, c)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)


def test_proj_bisect_bf16():
    key = jax.random.PRNGKey(0)
    kz, ka, kc = jax.random.split(key, 3)
    z = (jax.random.normal(kz, (16, 32)) * 5).astype(jnp.bfloat16)
    a = jax.random.uniform(ka, (16, 32), minval=0.1, maxval=4.0).astype(jnp.bfloat16)
    mask = jnp.ones((16, 32), jnp.bfloat16)
    c = jax.random.uniform(kc, (16,), minval=0.3, maxval=6.0).astype(jnp.bfloat16)
    got = proj_bisect(z, a, mask, c, interpret=True)
    want = ref.proj_rows_exact_np(
        z.astype(jnp.float32), a.astype(jnp.float32), mask, c.astype(jnp.float32)
    )
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=0.3)


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_proj_bisect_property_feasibility(seed):
    """Kernel output is always feasible: box + capacity + mask zeros."""
    key = jax.random.PRNGKey(seed)
    kz, ka, km, kc = jax.random.split(key, 4)
    z = jax.random.normal(kz, (8, 16)) * 10
    a = jax.random.uniform(ka, (8, 16), minval=0.05, maxval=3.0)
    mask = (jax.random.uniform(km, (8, 16)) < 0.7).astype(jnp.float32)
    c = jax.random.uniform(kc, (8,), minval=0.1, maxval=5.0)
    y = np.asarray(proj_bisect(z, a, mask, c, interpret=True))
    assert (y >= -1e-6).all()
    assert (y <= np.asarray(a) + 1e-5).all()
    assert (np.abs(y * (1 - np.asarray(mask))) < 1e-6).all()
    assert (y.sum(1) <= np.asarray(c) + 1e-4).all()


def test_proj_bisect_reduced_iters_accuracy():
    """The seeded bracket + secant finish keeps the kernel at exact-oracle
    accuracy with ITERS cut from 64 to ~20 (the perf lever the sorted sweep
    cannot give the TPU kernel, which has no efficient in-kernel sort)."""
    from repro.kernels.proj_bisect import ITERS

    assert ITERS <= 24  # the reduced count itself, not 64
    key = jax.random.PRNGKey(17)
    kz, ka, kc = jax.random.split(key, 3)
    z = jax.random.normal(kz, (64, 48)) * 20.0  # wide tau range
    a = jax.random.uniform(ka, (64, 48), minval=0.05, maxval=4.0)
    mask = jnp.ones((64, 48))
    c = jax.random.uniform(kc, (64,), minval=0.2, maxval=10.0)
    got = proj_bisect(z, a, mask, c, interpret=True)
    want = ref.proj_rows_exact_np(z, a, mask, c)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # bracket-width bound: capacity overshoot stays at f32-rounding scale
    assert (np.asarray(got).sum(1) <= np.asarray(c) + 1e-4).all()


# ------------------------------------------------------ sortscan projection --
@pytest.mark.parametrize("N,L", [(4, 8), (16, 24), (33, 130), (8, 1)])
def test_proj_sortscan_shapes(N, L):
    """The in-kernel breakpoint sweep is exact: <= 1e-6 of the float64
    numpy oracle (vs the bisect kernel's 5e-5)."""
    key = jax.random.fold_in(jax.random.PRNGKey(N), L)
    kz, ka, km, kc = jax.random.split(key, 4)
    z = jax.random.normal(kz, (N, L)) * 5
    a = jax.random.uniform(ka, (N, L), minval=0.1, maxval=4.0)
    mask = (jax.random.uniform(km, (N, L)) < 0.8).astype(jnp.float32)
    c = jax.random.uniform(kc, (N,), minval=0.3, maxval=6.0)
    got = proj_sortscan(z, a, mask, c, interpret=True)
    want = ref.proj_rows_exact_np(z, a, mask, c)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)


@pytest.mark.parametrize("row_block", list(autotune.ROW_BLOCKS))
def test_proj_sortscan_parity_every_autotuned_tile(row_block):
    """Oracle parity at EVERY tiling the autotuner may pick, and bitwise
    equality across tilings — rows are independent, so the tile sets the
    grid shape only, never the values (the autotune cache must not be able
    to change results, only speed)."""
    N, L = 33, 130
    key = jax.random.PRNGKey(7)
    kz, ka, km, kc = jax.random.split(key, 4)
    z = jax.random.normal(kz, (N, L)) * 5
    a = jax.random.uniform(ka, (N, L), minval=0.1, maxval=4.0)
    mask = (jax.random.uniform(km, (N, L)) < 0.8).astype(jnp.float32)
    c = jax.random.uniform(kc, (N,), minval=0.3, maxval=6.0)
    got = proj_sortscan(z, a, mask, c, row_block=row_block, interpret=True)
    want = ref.proj_rows_exact_np(z, a, mask, c)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    base = proj_sortscan(
        z, a, mask, c, row_block=autotune.ROW_BLOCKS[0], interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_proj_sortscan_property_feasibility(seed):
    key = jax.random.PRNGKey(seed)
    kz, ka, km, kc = jax.random.split(key, 4)
    z = jax.random.normal(kz, (8, 16)) * 10
    a = jax.random.uniform(ka, (8, 16), minval=0.05, maxval=3.0)
    mask = (jax.random.uniform(km, (8, 16)) < 0.7).astype(jnp.float32)
    c = jax.random.uniform(kc, (8,), minval=0.1, maxval=5.0)
    y = np.asarray(proj_sortscan(z, a, mask, c, interpret=True))
    assert (y >= -1e-6).all()
    assert (y <= np.asarray(a) + 1e-6).all()
    assert (np.abs(y * (1 - np.asarray(mask))) < 1e-6).all()
    assert (y.sum(1) <= np.asarray(c) + 1e-5).all()


def _run_in_kernel(fn, n_out, *xs):
    """``fn(*xs, lane_iota)`` on whole arrays inside an interpret-mode Pallas
    kernel: the lane rotations it uses lower only there."""
    def kernel(*refs):
        ins, outs = refs[:len(xs)], refs[len(xs):]
        idx = jax.lax.broadcasted_iota(jnp.int32, xs[0].shape, 1)
        res = fn(*(r[...] for r in ins), idx)
        for o, r in zip(outs, res if isinstance(res, tuple) else (res,)):
            o[...] = r

    shape = jax.ShapeDtypeStruct(xs[0].shape, jnp.float32)
    out = pl.pallas_call(kernel, out_shape=[shape] * n_out, interpret=True)(*xs)
    return [np.asarray(o) for o in out]


def test_bitonic_sort_pairs_unit():
    """The rotation-only bitonic network sorts ascending with the payload
    riding its value exactly (distinct keys)."""
    rng = np.random.default_rng(0)
    v = rng.normal(size=(3, 16)).astype(np.float32)
    d = rng.normal(size=(3, 16)).astype(np.float32)
    vs, ds = _run_in_kernel(
        sortscan._bitonic_sort_pairs, 2, jnp.asarray(v), jnp.asarray(d)
    )
    order = np.argsort(v, axis=1)
    np.testing.assert_array_equal(vs, np.take_along_axis(v, order, 1))
    np.testing.assert_array_equal(ds, np.take_along_axis(d, order, 1))


def test_scan_matmul_helpers_unit():
    """Cumsum, shift-by-one and lane rotation (the Mosaic-safe substitutes
    for scan, shift and gather) on a known row."""
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    cases = [
        (sortscan._cumsum, [[1.0, 3.0, 6.0, 10.0]]),
        (sortscan._shift1, [[0.0, 1.0, 2.0, 3.0]]),
        (lambda v, idx: sortscan._roll(v, 1), [[4.0, 1.0, 2.0, 3.0]]),
        (lambda v, idx: sortscan._roll(v, -1), [[2.0, 3.0, 4.0, 1.0]]),
    ]
    for fn, want in cases:
        (got,) = _run_in_kernel(fn, 1, x)
        np.testing.assert_array_equal(got, want)


def test_ops_proj_sortscan_dispatcher_paths():
    """Both dispatch arms of ops.proj_sortscan agree with the oracle: the
    off-TPU jnp sweep and the Pallas kernel under an explicitly pinned
    tiling (no cache read)."""
    key = jax.random.PRNGKey(11)
    kz, ka, kc = jax.random.split(key, 3)
    z = jax.random.normal(kz, (17, 40)) * 5
    a = jax.random.uniform(ka, (17, 40), minval=0.1, maxval=4.0)
    mask = jnp.ones((17, 40))
    c = jax.random.uniform(kc, (17,), minval=0.3, maxval=6.0)
    want = ref.proj_rows_exact_np(z, a, mask, c)
    got_jnp = ops.proj_sortscan(z, a, mask, c, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got_jnp), want, atol=1e-6)
    got_pl = ops.proj_sortscan(
        z, a, mask, c, use_pallas=True,
        tiling=autotune.KernelConfig(16, "sortscan", 0),
    )
    np.testing.assert_allclose(np.asarray(got_pl), want, atol=1e-6)


# --------------------------------------------------------------- oga step --
@pytest.mark.parametrize("N,L", [(6, 10), (24, 48)])
def test_oga_step_fused_vs_ref(N, L):
    key = jax.random.fold_in(jax.random.PRNGKey(N), L)
    ks = jax.random.split(key, 7)
    y = jax.random.uniform(ks[0], (N, L), maxval=2.0)
    a = jax.random.uniform(ks[1], (N, L), minval=0.5, maxval=3.0)
    mask = (jax.random.uniform(ks[2], (N, L)) < 0.8).astype(jnp.float32)
    y = jnp.minimum(y, a) * mask
    x = (jax.random.uniform(ks[3], (N, L)) < 0.7).astype(jnp.float32)
    kstar = (jax.random.uniform(ks[4], (N, L)) < 0.2).astype(jnp.float32)
    scal = jnp.stack(
        [
            jax.random.uniform(ks[5], (N,), minval=1.0, maxval=1.5),  # alpha
            jax.random.uniform(ks[6], (N,), minval=0.3, maxval=0.5),  # beta
            jax.random.uniform(ks[0], (N,), minval=1.0, maxval=8.0),  # c
            jnp.asarray(np.arange(N) % 4, jnp.float32),               # kind
            jnp.full((N,), 0.7),                                      # eta
        ],
        axis=1,
    )
    got = oga_step_fused(y, a, mask, x, kstar, scal, interpret=True)
    want = ref.oga_step_ref(y, a, mask, x, kstar, scal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("N,L", [(6, 10), (24, 48)])
def test_oga_step_method_ab_sortscan_vs_bisect(N, L):
    """The retired-default bisect stays available as method="bisect" for
    A/B: both methods match the reference, and each other."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(1), N), L
    )
    ks = jax.random.split(key, 7)
    y = jax.random.uniform(ks[0], (N, L), maxval=2.0)
    a = jax.random.uniform(ks[1], (N, L), minval=0.5, maxval=3.0)
    mask = (jax.random.uniform(ks[2], (N, L)) < 0.8).astype(jnp.float32)
    y = jnp.minimum(y, a) * mask
    x = (jax.random.uniform(ks[3], (N, L)) < 0.7).astype(jnp.float32)
    kstar = (jax.random.uniform(ks[4], (N, L)) < 0.2).astype(jnp.float32)
    scal = jnp.stack(
        [
            jax.random.uniform(ks[5], (N,), minval=1.0, maxval=1.5),
            jax.random.uniform(ks[6], (N,), minval=0.3, maxval=0.5),
            jax.random.uniform(ks[0], (N,), minval=1.0, maxval=8.0),
            jnp.asarray(np.arange(N) % 4, jnp.float32),
            jnp.full((N,), 0.7),
        ],
        axis=1,
    )
    want = np.asarray(ref.oga_step_ref(y, a, mask, x, kstar, scal))
    got_ss = oga_step_fused(
        y, a, mask, x, kstar, scal, method="sortscan", interpret=True
    )
    got_bi = oga_step_fused(
        y, a, mask, x, kstar, scal, method="bisect", interpret=True
    )
    np.testing.assert_allclose(np.asarray(got_ss), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_bi), want, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got_ss), np.asarray(got_bi), atol=5e-5
    )
    with pytest.raises(ValueError):
        oga_step_fused(
            y, a, mask, x, kstar, scal, method="newton", interpret=True
        )


def test_oga_step_fused_handles_infeasible_input():
    """y outside the box (e.g. warm-start from a stale allocation) must not
    NaN: utilities are defined on R_{>=0} and the kernel clamps like the
    reference (regression test for the bench-discovered edge)."""
    key = jax.random.PRNGKey(3)
    N, L = 8, 16
    y = jax.random.normal(key, (N, L)) * 10.0  # wildly infeasible
    a = jnp.full((N, L), 2.0)
    mask = jnp.ones((N, L))
    x = jnp.ones((N, L))
    kstar = jnp.zeros((N, L))
    scal = jnp.stack(
        [jnp.full((N,), 1.2), jnp.full((N,), 0.4), jnp.full((N,), 5.0),
         jnp.asarray(np.arange(N) % 4, jnp.float32), jnp.full((N,), 0.5)],
        axis=1,
    )
    got = oga_step_fused(y, a, mask, x, kstar, scal, interpret=True)
    want = ref.oga_step_ref(y, a, mask, x, kstar, scal)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_oga_step_scal_layout_guard():
    """scal wider than the kernel's 128-lane block must raise, and the
    documented column layout is importable from one place."""
    from repro.kernels.oga_step import NUM_SCAL, SCAL_COLUMNS, pack_scal

    assert SCAL_COLUMNS == ("alpha", "beta", "c", "kind", "eta")
    N, L = 8, 16
    ones = jnp.ones((N, L))
    cols = [jnp.full((N,), v) for v in (1.2, 0.4, 5.0, 0.0, 0.5)]
    scal = pack_scal(*cols)
    assert scal.shape == (N, NUM_SCAL)
    oga_step_fused(ones, ones, ones, ones, ones, scal, interpret=True)
    with pytest.raises(ValueError):
        oga_step_fused(
            ones, ones, ones, ones, ones, jnp.ones((N, 200)), interpret=True
        )


def test_oga_step_fused_equals_core_pipeline():
    """Fused kernel == core reward_grad + project on a real ClusterSpec."""
    from repro.core import projection, reward
    from repro.sched import trace

    spec = trace.build_spec(trace.TraceConfig(L=6, R=12, K=4, seed=3))
    key = jax.random.PRNGKey(0)
    from repro.core.graph import random_feasible_decision

    y = random_feasible_decision(spec, key)
    x = (jax.random.uniform(jax.random.PRNGKey(1), (6,)) < 0.7).astype(jnp.float32)
    eta = 0.5
    # core pipeline
    g = reward.reward_grad(spec, x, y)
    want = projection.project(spec, y + eta * g)
    # kernel layout: rows = (r, k) cells, lanes = ports
    L, R, K = spec.L, spec.R, spec.K
    s = jnp.sum(y * spec.mask[:, :, None], axis=1)  # (L, K)
    kstar = jax.nn.one_hot(jnp.argmax(spec.beta[None] * s, 1), K)  # (L, K)
    rows = lambda t: t.transpose(1, 2, 0).reshape(R * K, L)
    y_r = rows(y)
    a_r = jnp.broadcast_to(spec.a.T[None], (R, K, L)).reshape(R * K, L)
    m_r = jnp.broadcast_to(spec.mask.T[:, None], (R, K, L)).reshape(R * K, L)
    x_r = jnp.broadcast_to(x[None], (R * K, L))
    ks_r = jnp.broadcast_to(kstar.T[None], (R, K, L)).reshape(R * K, L)
    scal = jnp.stack(
        [
            spec.alpha.reshape(-1),
            jnp.broadcast_to(spec.beta[None], (R, K)).reshape(-1),
            spec.c.reshape(-1),
            jnp.broadcast_to(spec.kinds[None], (R, K)).reshape(-1).astype(jnp.float32),
            jnp.full((R * K,), eta),
        ],
        axis=1,
    )
    got = oga_step_fused(y_r, a_r, m_r, x_r, ks_r, scal, interpret=True)
    got_lrk = got.reshape(R, K, L).transpose(2, 0, 1)
    np.testing.assert_allclose(np.asarray(got_lrk), np.asarray(want), atol=5e-5)


# --------------------------------------------------------- flash attention -
@pytest.mark.parametrize(
    "B,S,H,G,hd",
    [(1, 128, 4, 2, 64), (2, 256, 4, 1, 64), (1, 256, 8, 8, 128), (2, 512, 2, 1, 64)],
)
def test_flash_attention_shapes(B, S, H, G, hd):
    key = jax.random.fold_in(jax.random.PRNGKey(B), S)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, hd))
    k = jax.random.normal(kk, (B, S, G, hd))
    v = jax.random.normal(kv, (B, S, G, hd))
    got = flash_attention(q, k, v, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("window,softcap", [(128, None), (None, 30.0), (128, 50.0)])
def test_flash_attention_window_softcap(window, softcap):
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    B, S, H, G, hd = 1, 256, 4, 2, 64
    q = jax.random.normal(kq, (B, S, H, hd))
    k = jax.random.normal(kk, (B, S, G, hd))
    v = jax.random.normal(kv, (B, S, G, hd))
    got = flash_attention(q, k, v, window=window, softcap=softcap, interpret=True)
    want = ref.flash_attention_ref(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    B, S, H, G, hd = 1, 128, 2, 1, 64
    q = jax.random.normal(kq, (B, S, H, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, G, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, G, hd)).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0.05
    )
