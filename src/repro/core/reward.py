"""Single-slot reward q(x, y) (paper eq. 7-8) and its gradient (eq. 30)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import utilities
from repro.core.graph import ClusterSpec


def service_rates(spec: ClusterSpec, y: jax.Array) -> jax.Array:
    """Speedup utility minus communication penalty per port (eq. 7 without
    the arrival multiplier): sum_{r,k} f_r^k(y) - max_k beta_k sum_r y^k.

    This is both the per-port reward factor and — for the job-lifecycle layer
    (sched.lifecycle) — the work-units-per-slot service rate an executing job
    extracts from its held allocation.
    """
    m = spec.mask[:, :, None]
    ym = y * m
    gain = jnp.sum(
        utilities.util_value(spec.kinds, spec.alpha[None, :, :], ym) * m,
        axis=(1, 2),
    )  # (L,)
    s = jnp.sum(ym, axis=1)  # (L, K) quota per (port, resource)
    penalty = jnp.max(spec.beta[None, :] * s, axis=1)  # (L,)
    return gain - penalty


def port_rewards(spec: ClusterSpec, x: jax.Array, y: jax.Array) -> jax.Array:
    """q_l(x, y) for every port (eq. 7, nice-setup separable form).

    Args:
      x: (L,) arrival indicators (float/int; §3.4 allows counts).
      y: (L, R, K) allocations.
    Returns: (L,) rewards.
    """
    return x.astype(y.dtype) * service_rates(spec, y)


def total_reward(spec: ClusterSpec, x: jax.Array, y: jax.Array) -> jax.Array:
    """q(x, y) = sum_l q_l (eq. 8)."""
    with obs.scope("reward"):
        return jnp.sum(port_rewards(spec, x, y))


def decompose(spec: ClusterSpec, x: jax.Array, y: jax.Array):
    """(total gain, total penalty) across ports — Fig. 6 decomposition."""
    m = spec.mask[:, :, None]
    ym = y * m
    gain = jnp.sum(
        utilities.util_value(spec.kinds, spec.alpha[None, :, :], ym) * m,
        axis=(1, 2),
    )
    s = jnp.sum(ym, axis=1)
    penalty = jnp.max(spec.beta[None, :] * s, axis=1)
    xf = x.astype(y.dtype)
    return jnp.sum(xf * gain), jnp.sum(xf * penalty)


def reward_grad(spec: ClusterSpec, x: jax.Array, y: jax.Array) -> jax.Array:
    """dq/dy (eq. 30): x_l ((f_r^k)'(y) - beta_k 1{k = k*_l}), masked.

    k*_l = argmax_k beta_k sum_r y_{(l,r)}^k (eq. 27); ties take the first
    index, a valid supergradient of the concave reward.
    """
    m = spec.mask[:, :, None]
    ym = y * m
    g = utilities.util_grad(spec.kinds, spec.alpha[None, :, :], ym)  # (L,R,K)
    s = jnp.sum(ym, axis=1)  # (L, K)
    kstar = jnp.argmax(spec.beta[None, :] * s, axis=1)  # (L,)
    is_kstar = jax.nn.one_hot(kstar, spec.K, dtype=y.dtype)  # (L, K)
    grad = g - spec.beta[None, None, :] * is_kstar[:, None, :]
    return x.astype(y.dtype)[:, None, None] * grad * m


def grad_norm_bound(spec: ClusterSpec) -> jax.Array:
    """Upper bound of ||grad q|| (eq. 45): sum_l sum_{r in R_l} ((b*)^2 + K (w_r*)^2)."""
    w = utilities.util_grad_at_zero(spec.kinds, spec.alpha)  # (R, K)
    w_star = jnp.max(w, axis=1)  # (R,) varpi_r^*
    beta_star = jnp.max(spec.beta)
    per_lr = spec.mask * (beta_star**2 + spec.K * w_star[None, :] ** 2)
    return jnp.sqrt(jnp.sum(per_lr))


def diameter_bound(spec: ClusterSpec) -> jax.Array:
    """diam(Y) upper bound (eq. 48): sqrt(2 sum_k a_bar^k sum_r c_r^k)."""
    a_bar = jnp.max(spec.a, axis=0)  # (K,)
    return jnp.sqrt(2.0 * jnp.sum(a_bar * jnp.sum(spec.c, axis=0)))
