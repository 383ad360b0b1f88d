"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Stages live on the 'model' axis (one stage = n_layers/S consecutive layers);
microbatches stream through a tick loop: at tick t, stage s processes
microbatch m = t - s (bubble ticks compute masked garbage — the classic
(S-1)/(M+S-1) bubble overhead). Backward falls out of autodiff (reversed
permutes), with GPipe's per-microbatch activation footprint.

Demonstration-grade (DESIGN.md §5 notes PP is not required for the assigned
meshes): validated against the scanned reference in tests/test_pipeline.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import transformer as tf


def _stage_forward(stage_params, cfg: ArchConfig, x, positions, windows):
    """Run this stage's (L/S,) stacked layers locally (no remat — GPipe
    stores per-microbatch boundaries; microbatches keep footprints small)."""

    def body(h, inp):
        p, w = inp
        h2, _ = tf.block_forward(p, cfg, h, positions, w)
        return h2, None

    x, _ = jax.lax.scan(body, x, (stage_params, windows))
    return x


def pipeline_forward(
    stacked_blocks,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    mesh,
    n_micro: int,
    axis: str = "model",
):
    """x: (B, S, d) -> (B, S, d) through n_layers split into mesh.shape[axis]
    pipeline stages with ``n_micro`` microbatches."""
    S_stages = mesh.shape[axis]
    B = x.shape[0]
    assert cfg.n_layers % S_stages == 0 and B % n_micro == 0
    L_per = cfg.n_layers // S_stages
    Bm = B // n_micro

    windows = tf.layer_windows(cfg)
    # reorganise (n_layers, ...) -> (stages, L_per, ...); dim0 sharded on axis
    restage = lambda t: t.reshape((S_stages, L_per) + t.shape[1:])
    staged = jax.tree.map(restage, stacked_blocks)
    wst = restage(windows)
    xm = x.reshape((n_micro, Bm) + x.shape[1:])
    pos_m = positions[:Bm]

    p_specs = jax.tree.map(lambda _: P(axis), staged)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(p_specs, P(axis), P(None), P(None)),
        out_specs=P(None),
        check_vma=False,
    )
    def run(stage_params, stage_windows, xm_local, pos_local):
        sid = jax.lax.axis_index(axis)
        sp = jax.tree.map(lambda t: t[0], stage_params)  # (L_per, ...)
        sw = stage_windows[0]
        n_ticks = n_micro + S_stages - 1
        fwd_perm = [(i, i + 1) for i in range(S_stages - 1)]

        def tick(carry, t):
            a_recv, outputs = carry
            m = t - sid  # microbatch index this stage works on
            active = (m >= 0) & (m < n_micro)
            inp = jnp.where(
                sid == 0,
                xm_local[jnp.clip(t, 0, n_micro - 1)],
                a_recv,
            )
            out = _stage_forward(sp, cfg, inp, pos_local, sw)
            out = jnp.where(active, out, inp)
            # last stage banks its finished microbatch
            is_last = sid == S_stages - 1
            outputs = jax.lax.cond(
                active & is_last,
                lambda o: o.at[jnp.clip(m, 0, n_micro - 1)].set(out),
                lambda o: o,
                outputs,
            )
            a_next = jax.lax.ppermute(out, axis, fwd_perm)
            return (a_next, outputs), None

        a0 = jnp.zeros_like(xm_local[0])
        outs0 = jnp.zeros_like(xm_local)
        (_, outputs), _ = jax.lax.scan(
            tick, (a0, outs0), jnp.arange(n_ticks)
        )
        # only the last stage holds real outputs; broadcast via masked psum
        mask = (jax.lax.axis_index(axis) == S_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    out = run(staged, wst, xm, pos_m)
    return out.reshape(x.shape)
