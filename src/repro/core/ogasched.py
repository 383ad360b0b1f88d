"""OGASCHED (paper Alg. 1): online gradient ascent + fast projection."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import reward
from repro.core.graph import ClusterSpec, random_feasible_decision
from repro.kernels import ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OGAState:
    y: jax.Array     # (L, R, K) current decision
    eta: jax.Array   # scalar learning rate
    t: jax.Array     # scalar step counter


def init_state(
    spec: ClusterSpec, eta0: float, key: Optional[jax.Array] = None
) -> OGAState:
    if key is None:
        y = jnp.zeros((spec.L, spec.R, spec.K), spec.a.dtype)
    else:
        y = random_feasible_decision(spec, key)
    return OGAState(
        y=y, eta=jnp.asarray(eta0, spec.a.dtype), t=jnp.zeros((), jnp.int32)
    )


def oga_step(
    spec: ClusterSpec,
    state: OGAState,
    x: jax.Array,
    decay: float | jax.Array,
    backend: str = "reference",
    operands=None,
) -> tuple[OGAState, jax.Array]:
    """One slot: observe x(t), collect q(x(t), y(t)), ascend, project.

    ``backend`` selects the update implementation (kernels.ops): "reference"
    runs grad (eq. 30) -> ascent (Alg. 1 step 5) -> projection (steps 6-31)
    as separate passes; "fused" runs the single-pass Pallas kernel.
    Returns (next_state, reward_at_t).
    """
    q_t = reward.total_reward(spec, x, state.y)
    with obs.scope("oga.update"):
        y_next = ops.oga_update_spec(
            spec, state.y, x, state.eta, backend=backend, operands=operands,
        )
    new = OGAState(y=y_next, eta=state.eta * decay, t=state.t + 1)
    return new, q_t


@partial(jax.jit, static_argnames=("return_traj", "backend"))
def run(
    spec: ClusterSpec,
    arrivals: jax.Array,
    eta0: float | jax.Array,
    decay: float | jax.Array = 0.9999,
    y0: Optional[jax.Array] = None,
    return_traj: bool = False,
    backend: str = "auto",
):
    """Run OGASCHED over an arrival trajectory.

    Args:
      arrivals: (T, L) arrival indicators (or counts via §3.4 expansion).
      eta0, decay: initial learning rate and decay lambda (paper Tab. 2).
        Both may be traced arrays, so hyperparameter grids vmap (sched.sweep).
      backend: "fused" | "reference" | "auto" — see kernels.ops.oga_update_spec.
    Returns:
      rewards: (T,) per-slot rewards q(x(t), y(t)).
      y_final: (L, R, K); plus the full trajectory if ``return_traj``.
    """
    backend = ops.resolve_oga_backend(backend)
    state = init_state(spec, eta0)
    if y0 is not None:
        state = dataclasses.replace(state, y=y0)
    operands = ops.pack_spec_operands(spec) if backend == "fused" else None

    def body(s, x):
        s2, q_t = oga_step(spec, s, x, decay, backend, operands)
        out = (q_t, s2.y) if return_traj else (q_t, jnp.zeros((), s2.y.dtype))
        return s2, out

    final, (rewards, traj) = jax.lax.scan(body, state, arrivals)
    if return_traj:
        return rewards, final.y, traj
    return rewards, final.y


@partial(jax.jit, static_argnames=("use_pallas", "tiling"))
def run_batch(
    spec: ClusterSpec,
    arrivals: jax.Array,
    eta0: jax.Array,
    decay: jax.Array,
    use_pallas: bool | None = None,
    tiling=None,
):
    """Run OGASCHED over a stacked grid of G configurations, grid-flattened.

    The fused-backend twin of ``vmap(run)``: instead of vmapping G
    independent scans, one scan advances all configurations together and
    each step issues ONE fused row-kernel call over N = G*R*K rows
    (ops.oga_update_batch) — on TPU a single pallas_call per step for the
    whole chunk, off-TPU one packed-row jnp update with the exact sorted
    projection. Static operands are packed once, before the scan.

    Args:
      spec: stacked ClusterSpec (every leaf leading (G,)).
      arrivals: (G, T, L); eta0, decay: (G,) (traced, so hyperparameter
        axes sweep).
      tiling: optional static ``kernels.autotune.KernelConfig`` pinning the
        Pallas tiling for every step's fused call (hashable NamedTuple, so
        it rides as a jit static); default resolves from the autotune
        cache on the packed shape.
    Returns:
      rewards: (G, T) per-slot rewards; y_final: (G, L, R, K).
    """
    _, L, R = spec.mask.shape
    K = spec.a.shape[2]
    G, T, _ = arrivals.shape
    dtype = spec.a.dtype
    y0 = jnp.zeros((G, L, R, K), dtype)
    eta0 = jnp.broadcast_to(jnp.asarray(eta0, dtype), (G,))
    decay = jnp.broadcast_to(jnp.asarray(decay, dtype), (G,))
    operands = ops.pack_spec_operands_batch(spec)

    def body(carry, x_t):
        y, eta = carry
        q_t = jax.vmap(reward.total_reward)(spec, x_t, y)
        with obs.scope("oga.update"):
            y_next = ops.oga_update_batch(
                spec, y, x_t, eta, operands=operands, use_pallas=use_pallas,
                tiling=tiling,
            )
        return (y_next, eta * decay), q_t

    (y_final, _), qs = jax.lax.scan(
        body, (y0, eta0), jnp.swapaxes(arrivals, 0, 1)
    )
    return jnp.swapaxes(qs, 0, 1), y_final


def eta_theoretical(spec: ClusterSpec, T: int) -> jax.Array:
    """eq. 50: eta = diam(Y) / (||grad q|| sqrt(T)) with the Thm. 1 bounds."""
    return reward.diameter_bound(spec) / (
        reward.grad_norm_bound(spec) * jnp.sqrt(jnp.asarray(float(T)))
    )
