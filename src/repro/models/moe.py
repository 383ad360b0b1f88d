"""Mixture-of-Experts layer: top-k routing with sort-based capacity dispatch.

Dispatch avoids (T, E, C) one-hot tensors (infeasible at E=384): tokens are
argsorted by expert id, ranked within their expert group via searchsorted,
and scattered into an (E, C, d) buffer — O(Tk log Tk) and matmul-rich, which
suits both the MXU and XLA SPMD expert parallelism (experts sharded over the
``model`` axis; the scatter/gather become all-to-alls).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import he_init
from repro.train.meshctx import constrain


def init_moe(key, d_model: int, d_expert: int, n_experts: int, n_shared: int, dtype):
    kr, ke, ks = jax.random.split(key, 3)
    kg, ku, kd = jax.random.split(ke, 3)
    p = {
        "router": he_init(kr, (d_model, n_experts), d_model, jnp.float32),
        "gate": he_init(kg, (n_experts, d_model, d_expert), d_model, dtype),
        "up": he_init(ku, (n_experts, d_model, d_expert), d_model, dtype),
        "down": he_init(kd, (n_experts, d_expert, d_model), d_expert, dtype),
    }
    if n_shared:
        sg, su, sd = jax.random.split(ks, 3)
        p["shared"] = {
            "gate": he_init(sg, (d_model, n_shared * d_expert), d_model, dtype),
            "up": he_init(su, (d_model, n_shared * d_expert), d_model, dtype),
            "down": he_init(sd, (n_shared * d_expert, d_model), d_expert, dtype),
        }
    return p


def apply_moe(
    p: dict,
    x: jax.Array,
    top_k: int,
    capacity_factor: float = 1.25,
) -> jax.Array:
    """x: (T, d) tokens -> (T, d). Capacity C = ceil(T * k / E * cf)."""
    T, d = x.shape
    E = p["router"].shape[1]
    C = max(int(T * top_k / E * capacity_factor), top_k)

    logits = x.astype(jnp.float32) @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, eidx = jax.lax.top_k(probs, top_k)  # (T, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch ------------------------------------------
    flat_e = eidx.reshape(-1)                       # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), top_k)       # (T*k,)
    flat_w = gate_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # rank within the expert group = i - first index of that expert id
    first = jnp.searchsorted(se, se, side="left")
    rank = jnp.arange(T * top_k) - first            # (T*k,)
    keep = rank < C                                  # overflow drops
    slot_e = jnp.where(keep, se, 0)
    slot_c = jnp.where(keep, rank, 0)

    xbuf = jnp.zeros((E, C, d), x.dtype)
    xbuf = xbuf.at[slot_e, slot_c].add(
        jnp.where(keep[:, None], x[st], 0.0).astype(x.dtype)
    )
    # EP sharding: experts over 'model', capacity over 'data' — keeps the
    # (E, C, d) dispatch buffers at ~d_model*C_local per device
    xbuf = constrain(xbuf, "model", "data", None)

    # ---- expert computation (batched matmuls over E) ------------------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xbuf, p["gate"]))
    g = constrain(g, "model", "data", None)
    u = jnp.einsum("ecd,edf->ecf", xbuf, p["up"])
    ybuf = jnp.einsum("ecf,efd->ecd", g * u, p["down"])  # (E, C, d)
    ybuf = constrain(ybuf, "model", "data", None)

    # ---- combine -------------------------------------------------------
    vals = ybuf[slot_e, slot_c] * (sw * keep)[:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[st].add(vals)

    if "shared" in p:
        s = p["shared"]
        gs = jax.nn.silu(x @ s["gate"]) * (x @ s["up"])
        out = out + gs @ s["down"]
    return out


# --------------------------------------------------------------- EP path ---
def _local_dispatch_combine(p_local, x_flat, top_k, cf, e0, E, E_loc):
    """Device-local capacity dispatch over the expert range [e0, e0+E_loc).

    Returns this shard's partial output (T, d) — tokens routed to experts
    outside the range contribute zero here and are summed in by the
    psum_scatter across the 'model' axis.
    """
    T, d = x_flat.shape
    C = max(int(T * top_k / E * cf), top_k)
    logits = x_flat.astype(jnp.float32) @ p_local["router"]  # (T, E) full
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, eidx = jax.lax.top_k(probs, top_k)  # (T, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_e = eidx.reshape(-1) - e0                   # local expert ids
    mine = (flat_e >= 0) & (flat_e < E_loc)
    flat_e = jnp.where(mine, flat_e, E_loc)          # sentinel sorts last
    flat_t = jnp.repeat(jnp.arange(T), top_k)
    order = jnp.argsort(flat_e, stable=True)
    se, st = flat_e[order], flat_t[order]
    first = jnp.searchsorted(se, se, side="left")
    rank = jnp.arange(T * top_k) - first
    keep = (rank < C) & (se < E_loc)
    # invalid entries get out-of-range coordinates -> dropped by mode="drop"
    slot_e = jnp.where(keep, se, E_loc)
    slot_c = jnp.where(keep, rank, C)

    # int-only index plumbing: never materialise a (T*k, d) features tensor
    tok_for_slot = jnp.full((E_loc, C), T, jnp.int32).at[slot_e, slot_c].set(
        st.astype(jnp.int32), mode="drop"
    )
    slot_valid = jnp.zeros((E_loc, C), x_flat.dtype).at[slot_e, slot_c].set(
        1.0, mode="drop"
    )
    xpad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)], 0)
    xbuf = xpad[tok_for_slot] * slot_valid[..., None]     # (E_loc, C, d)

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xbuf, p_local["gate"]))
    u = jnp.einsum("ecd,edf->ecf", xbuf, p_local["up"])
    ybuf = jnp.einsum("ecf,efd->ecd", g * u, p_local["down"])

    # per-(t, k) slot coordinates, recovered by unsorting (ints only)
    inv = jnp.zeros((T * top_k,), jnp.int32).at[order].set(
        jnp.arange(T * top_k, dtype=jnp.int32)
    )
    flat_sc = jnp.where(keep, rank, 0)[inv].reshape(T, top_k)
    flat_se = jnp.where(mine, eidx.reshape(-1) - e0, 0).reshape(T, top_k)
    w_eff = gate_w.astype(x_flat.dtype) * keep[inv].reshape(T, top_k).astype(
        x_flat.dtype
    )
    out = jnp.zeros((T, d), x_flat.dtype)
    for j in range(top_k):  # k bounded gathers of (T, d) — no (T*k, d) blowup
        out = out + w_eff[:, j, None] * ybuf[flat_se[:, j], flat_sc[:, j]]
    return out


def apply_moe_ep(p, x, cfg, mesh):
    """Expert-parallel MoE under shard_map (DESIGN.md §5 EP).

    x: (B, S, d) with the sequence-parallel carry sharding (dp, 'model', _).
    Experts are sharded over 'model'; tokens of each DP shard are gathered
    across 'model', routed to the local expert slice, and partial outputs are
    reduce-scattered back to the SP layout (psum fallback when S < tp).
    """
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    tp = mesh.shape["model"]
    B, S, d = x.shape
    E = cfg.n_experts
    E_loc = E // tp
    seq_shardable = S % tp == 0 and S >= tp

    x_spec = P(dp, "model" if seq_shardable else None, None)
    p_specs = {
        "router": P(None, None),
        "gate": P("model", None, None),
        "up": P("model", None, None),
        "down": P("model", None, None),
    }
    if "shared" in p:
        p_specs["shared"] = {k: P(None, None) for k in p["shared"]}

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    def f(p_local, x_local):
        if seq_shardable:
            xg = jax.lax.all_gather(x_local, "model", axis=1, tiled=True)
        else:
            xg = x_local
        Bl, Sg, _ = xg.shape
        e0 = jax.lax.axis_index("model") * E_loc
        part = _local_dispatch_combine(
            p_local, xg.reshape(Bl * Sg, d), cfg.top_k, cfg.capacity_factor,
            e0, E, E_loc,
        ).reshape(Bl, Sg, d)
        if seq_shardable:
            out = jax.lax.psum_scatter(
                part, "model", scatter_dimension=1, tiled=True
            )
        else:
            out = jax.lax.psum(part, "model")
        if "shared" in p_local:
            s = p_local["shared"]
            xs = x_local
            gs = jax.nn.silu(xs @ s["gate"]) * (xs @ s["up"])
            out = out + gs @ s["down"]
        return out

    return f(p, x)


def apply_mlp_ep(p, x, cfg, mesh):
    """Dense SwiGLU under shard_map: one bf16 seq all-gather in + one bf16
    psum_scatter out, with the d_ff dimension tensor-parallel over 'model'.
    Replaces XLA's f32 partial-sum all-reduces after the down-projection
    (~4x wire bytes each) — §Perf qwen2 iteration."""
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    tp = mesh.shape["model"]
    B, S, d = x.shape
    d_ff = p["gate"].shape[1]
    seq_shardable = S % tp == 0 and S >= tp
    if not seq_shardable or d_ff % tp != 0:
        from repro.models.layers import swiglu_apply

        return swiglu_apply(p, x)

    x_spec = P(dp, "model", None)
    p_specs = {"gate": P(None, "model"), "up": P(None, "model"),
               "down": P("model", None)}

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(p_specs, x_spec),
        out_specs=x_spec, check_vma=False,
    )
    def f(p_local, x_local):
        xg = jax.lax.all_gather(x_local, "model", axis=1, tiled=True)
        g = jax.nn.silu(xg @ p_local["gate"])
        part = (g * (xg @ p_local["up"])) @ p_local["down"]
        return jax.lax.psum_scatter(part, "model", scatter_dimension=1, tiled=True)

    return f(p, x)


def apply_moe_auto(p, x, cfg):
    """Pick EP (mesh with a 'model' axis active) or the single-device path."""
    from repro.train.meshctx import current_mesh

    mesh = current_mesh()
    if (
        mesh is not None
        and "model" in mesh.axis_names
        and cfg.n_experts % mesh.shape["model"] == 0
    ):
        return apply_moe_ep(p, x, cfg, mesh)
    B, S, d = x.shape
    return apply_moe(p, x.reshape(B * S, d), cfg.top_k, cfg.capacity_factor).reshape(
        B, S, d
    )
