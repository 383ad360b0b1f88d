"""Distributed OGASCHED step via shard_map (paper §3.2 'parallel
sub-procedures', mapped onto a real device mesh).

Sharding: instances R are sharded across mesh devices; each device holds
y_local (L, R/p, K). The per-(r,k) fast projection is *fully local*. The only
cross-device dependency is the per-(l,k) quota s_{l,k} = sum_r y for the
penalty argmax k* (eq. 27) — one psum per step. This is the paper's
thread-level parallelism re-expressed as SPMD + a single all-reduce.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import projection, utilities
from repro.core.graph import ClusterSpec


def _sharded_step(spec_local: ClusterSpec, y_local, x, eta, axis: str):
    """Device-local OGA step body; runs under shard_map over ``axis``."""
    m = spec_local.mask[:, :, None]
    ym = y_local * m
    s_local = jnp.sum(ym, axis=1)                      # (L, K) partial quota
    s = jax.lax.psum(s_local, axis)                    # the one collective
    kstar = jnp.argmax(spec_local.beta[None, :] * s, axis=1)
    is_kstar = jax.nn.one_hot(kstar, spec_local.K, dtype=y_local.dtype)
    g = utilities.util_grad(spec_local.kinds, spec_local.alpha[None], ym)
    grad = (g - spec_local.beta[None, None, :] * is_kstar[:, None, :]) * m
    grad = x.astype(y_local.dtype)[:, None, None] * grad
    z = y_local + eta * grad
    # local projection: per-(r,k) cells live entirely on this shard. The
    # exact sorted sweep is shard_map-safe — it evaluates breakpoints with
    # max/where reductions only, never the sort primitive that jax 0.4.37's
    # XLA:CPU miscompiles inside shard_map+fori_loop (see baselines._rank_order).
    y_next = projection.project_sorted(
        z, spec_local.a, spec_local.c, spec_local.mask
    )
    # local reward contribution (gain separable; penalty needs global s)
    gain_l = jnp.sum(
        utilities.util_value(spec_local.kinds, spec_local.alpha[None], ym) * m,
        axis=(1, 2),
    )
    gain = jax.lax.psum(gain_l, axis)
    penalty = jnp.max(spec_local.beta[None, :] * s, axis=1)
    q_t = jnp.sum(x.astype(y_local.dtype) * (gain - penalty))
    return y_next, q_t


def make_distributed_step(spec: ClusterSpec, mesh: Mesh, axis: str = "data"):
    """Build a pjit-able distributed OGA step.

    The returned fn maps (y, x, eta) -> (y_next, q_t) with y sharded
    P(None, axis, None) — instances split over ``axis``.
    """
    pspec_y = P(None, axis, None)
    spec_shardings = ClusterSpec(
        mask=P(None, axis),
        a=P(None, None),
        c=P(axis, None),
        alpha=P(axis, None),
        beta=P(None),
        kinds=P(None),
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec_shardings, pspec_y, P(None), P()),
        out_specs=(pspec_y, P()),
    )
    def step(spec_local, y_local, x, eta):
        return _sharded_step(spec_local, y_local, x, eta, axis)

    return step


def shard_spec(spec: ClusterSpec, mesh: Mesh, axis: str = "data") -> ClusterSpec:
    """Place a ClusterSpec with instances sharded over ``axis``."""
    put = lambda v, p: jax.device_put(v, NamedSharding(mesh, p))
    return ClusterSpec(
        mask=put(spec.mask, P(None, axis)),
        a=put(spec.a, P(None, None)),
        c=put(spec.c, P(axis, None)),
        alpha=put(spec.alpha, P(axis, None)),
        beta=put(spec.beta, P(None)),
        kinds=put(spec.kinds, P(None)),
    )
