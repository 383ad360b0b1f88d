"""Plain reference of the scheduler's semantics, written apart from the program.

Nothing here imports the program. It holds:

* the trace synthesis, copied from the program's host path (numpy
  ``SeedSequence`` streams, used by ``simulator.run_all``) and device path
  (``jax.random`` keys folded per stream, used by large streamed sweeps), so
  that the reference rebuilds from a seed the very deployments the program
  ran;
* OGASched (the paper's Alg. 1): reward (eq. 7-8), gradient (eq. 30),
  ascent and the exact projection onto the per-(r, k) capacity sets
  (eq. 32), the projection by bisection on the water level with an exact
  linear finish (the program sorts breakpoints; this is a second method);
* the four heuristics of the paper's Sec. 4 (DRF, fairness, bin-packing,
  spreading) with the placement rules, requested parallelism and tie-breaks
  the program documents.

Every scheduling function takes a ``dtype``: float32 is the reference,
bfloat16 the lower-precision control that ``correct`` must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# trace synthesis ----------------------------------------------------------

STREAMS = ("spec", "arrivals", "works", "faults", "cluster")
BURST_LEN = 20
UTILITY_KINDS = {"linear": 0, "log": 1, "reciprocal": 2, "poly": 3}
SEED_KINDS = 4


def stream_rng(seed: int, stream: str) -> np.random.Generator:
    children = np.random.SeedSequence(seed).spawn(len(STREAMS))
    return np.random.default_rng(children[STREAMS.index(stream)])


def spec_kinds(tc: dict) -> np.ndarray:
    if tc["utility"] == "mixed":
        return np.arange(tc["K"]) % SEED_KINDS
    return np.full(tc["K"], UTILITY_KINDS[tc["utility"]])


def spec_beta(tc: dict) -> np.ndarray:
    return np.linspace(tc["beta_range"][0], tc["beta_range"][1], tc["K"])


def host_spec(tc: dict, templates: dict) -> dict:
    """One deployment as numpy arrays: mask (L, R), a (L, K), c (R, K),
    alpha (R, K), beta (K,), kinds (K,)."""
    L, R, K = tc["L"], tc["R"], tc["K"]
    machines = np.asarray(templates["machines"], np.float64)
    jobs = np.asarray(templates["jobs"], np.float64)
    rng = stream_rng(tc["seed"], "spec")
    t_idx = rng.integers(0, len(machines), R)
    c = machines[t_idx][:, :K] * rng.uniform(0.8, 1.2, (R, K))
    c = np.maximum(c, 1.0)
    a = jobs[np.arange(L) % len(jobs)][:, :K] * rng.uniform(0.9, 1.1, (L, K))
    a = np.maximum(a, 0.25) * tc["contention"] / 10.0
    reach = ((a[:, None, :] > 0) & (c[None, :, :] > 0)).any(-1)
    mask = (rng.uniform(size=(L, R)) < tc["density"]) & reach
    empty_l = np.nonzero(~mask.any(axis=1))[0]
    if empty_l.size:
        mask[empty_l, rng.integers(0, R, size=empty_l.size)] = True
    empty_r = np.nonzero(~mask.any(axis=0))[0]
    if empty_r.size:
        mask[rng.integers(0, L, size=empty_r.size), empty_r] = True
    alpha = rng.uniform(*tc["alpha_range"], (R, K))
    return {
        "mask": mask.astype(np.float32), "a": a.astype(np.float32),
        "c": c.astype(np.float32), "alpha": alpha.astype(np.float32),
        "beta": spec_beta(tc).astype(np.float32),
        "kinds": spec_kinds(tc).astype(np.int32),
    }


def host_arrivals(tc: dict) -> np.ndarray:
    """(T, L) Bernoulli arrivals: rho thinned by a diurnal wave, with
    BURST_LEN-slot bursts in which a port fires with probability 0.95."""
    T, L = tc["T"], tc["L"]
    rng = stream_rng(tc["seed"], "arrivals")
    base = np.full((T, L), tc["rho"])
    if tc["diurnal"]:
        t = np.arange(T)[:, None]
        phase = rng.uniform(0, 2 * np.pi, (1, L))
        base = base * (0.75 + 0.25 * np.sin(2 * np.pi * t / 288.0 + phase))
    starts = rng.uniform(size=(T, L)) < tc["burst_prob"]
    cum = np.cumsum(starts, axis=0)
    burst = (cum - np.pad(cum, ((BURST_LEN, 0), (0, 0)))[:T]) > 0
    p = np.clip(np.where(burst, 0.95, base), 0.0, 1.0)
    return (rng.uniform(size=p.shape) < p).astype(np.float32)


def _stream_key(seed, stream: str):
    return jax.random.fold_in(jax.random.PRNGKey(seed), STREAMS.index(stream))


@functools.lru_cache(maxsize=None)
def _device_generator(L, R, K, T, density, alpha_range, diurnal, burst_prob,
                      machines, jobs):
    machines = np.asarray(machines, np.float32)[:, :K]
    jobs = np.asarray(jobs, np.float32)[:, :K]

    def one(seed, rho, contention, kinds, beta):
        k_c, k_cj, k_aj, k_mask, k_row, k_col, k_alpha = jax.random.split(
            _stream_key(seed, "spec"), 7)
        t_idx = jax.random.randint(k_c, (R,), 0, machines.shape[0])
        c = jnp.asarray(machines)[t_idx] * jax.random.uniform(
            k_cj, (R, K), minval=0.8, maxval=1.2)
        c = jnp.maximum(c, 1.0)
        a = jnp.asarray(jobs)[jnp.arange(L) % jobs.shape[0]] * \
            jax.random.uniform(k_aj, (L, K), minval=0.9, maxval=1.1)
        a = jnp.maximum(a, 0.25) * contention / 10.0
        reach = ((a[:, None, :] > 0) & (c[None, :, :] > 0)).any(-1)
        mask = (jax.random.uniform(k_mask, (L, R)) < density) & reach
        row_fix = jax.nn.one_hot(jax.random.randint(k_row, (L,), 0, R), R,
                                 dtype=jnp.bool_)
        mask = mask | (~mask.any(axis=1, keepdims=True) & row_fix)
        col_fix = jax.nn.one_hot(jax.random.randint(k_col, (R,), 0, L), L,
                                 dtype=jnp.bool_).T
        mask = mask | (~mask.any(axis=0, keepdims=True) & col_fix)
        alpha = jax.random.uniform(k_alpha, (R, K), minval=alpha_range[0],
                                   maxval=alpha_range[1])
        k_phase, k_start, k_draw = jax.random.split(
            _stream_key(seed, "arrivals"), 3)
        base = jnp.full((T, L), rho, jnp.float32)
        if diurnal:
            t = jnp.arange(T, dtype=jnp.float32)[:, None]
            phase = jax.random.uniform(k_phase, (1, L), minval=0.0,
                                       maxval=2.0 * jnp.pi)
            base = base * (0.75 + 0.25 * jnp.sin(
                2.0 * jnp.pi * t / 288.0 + phase))
        starts = jax.random.uniform(k_start, (T, L)) < burst_prob
        cum = jnp.cumsum(starts.astype(jnp.int32), axis=0)
        burst = (cum - jnp.pad(cum, ((BURST_LEN, 0), (0, 0)))[:T]) > 0
        p = jnp.clip(jnp.where(burst, 0.95, base), 0.0, 1.0)
        x = (jax.random.uniform(k_draw, (T, L)) < p).astype(jnp.float32)
        spec = {"mask": mask.astype(jnp.float32), "a": a.astype(jnp.float32),
                "c": c.astype(jnp.float32), "alpha": alpha.astype(jnp.float32),
                "beta": beta.astype(jnp.float32),
                "kinds": kinds.astype(jnp.int32)}
        return spec, x

    return jax.jit(jax.vmap(one))


def device_traces(tcs: list[dict], templates: dict):
    """Stacked (spec, arrivals) of deployments that share every static
    parameter, drawn on the default device from each one's seed."""
    t0 = tcs[0]
    gen = _device_generator(
        t0["L"], t0["R"], t0["K"], t0["T"], t0["density"],
        tuple(t0["alpha_range"]), bool(t0["diurnal"]), t0["burst_prob"],
        tuple(map(tuple, templates["machines"])),
        tuple(map(tuple, templates["jobs"])))
    return gen(
        jnp.asarray([t["seed"] for t in tcs], jnp.uint32),
        jnp.asarray([t["rho"] for t in tcs], jnp.float32),
        jnp.asarray([t["contention"] for t in tcs], jnp.float32),
        jnp.asarray(np.stack([spec_kinds(t) for t in tcs]), jnp.int32),
        jnp.asarray(np.stack([spec_beta(t) for t in tcs]), jnp.float32))


# reward and OGASched ------------------------------------------------------

_BIG = 1e30
PROJECTION_ITERS = 48  # halvings of [0, max z]: past f32 resolution


def _select(kinds, branches):
    out = jnp.zeros_like(branches[0])
    for kind, b in enumerate(branches):
        out = jnp.where(kinds == kind, b, out)
    return out


def utility(kinds, alpha, y):
    """f_r^k(y), eq. 51: linear, log, reciprocal, poly."""
    y = jnp.maximum(y, 0)
    return _select(kinds, [alpha * y, alpha * jnp.log1p(y),
                           1 / alpha - 1 / (y + alpha),
                           alpha * jnp.sqrt(y + 1) - alpha])


def utility_grad(kinds, alpha, y):
    y = jnp.maximum(y, 0)
    return _select(kinds, [alpha + 0 * y, alpha / (1 + y),
                           1 / jnp.square(y + alpha),
                           alpha / (2 * jnp.sqrt(y + 1))])


def reward(spec, x, y):
    """q(x, y) = sum_l x_l (sum_{r,k} f(y) - max_k beta_k sum_r y), eq. 7-8."""
    m = spec["mask"][:, :, None]
    ym = y * m
    gain = jnp.sum(utility(spec["kinds"], spec["alpha"][None], ym) * m,
                   axis=(1, 2))
    penalty = jnp.max(spec["beta"][None] * jnp.sum(ym, axis=1), axis=1)
    return jnp.sum(x * (gain - penalty))


def project(z, a, c, mask):
    """Euclidean projection of z (L, R, K) onto
    {0 <= y_l <= a_l^k, sum_{l in L_r} y_l <= c_r^k} for every (r, k):
    y = clip(z - tau, 0, a) with the water level tau bracketed by bisection
    and then solved exactly on its linear segment."""
    A = a[:, None, :]
    M = mask[:, :, None]
    box = jnp.clip(z, 0, A) * M
    need = jnp.sum(box, axis=0) > c
    g = lambda tau: jnp.sum(jnp.clip(z - tau[None], 0, A) * M, axis=0)

    def halve(_, lh):
        lo, hi = lh
        mid = (lo + hi) / 2
        big = g(mid) > c
        return jnp.where(big, mid, lo), jnp.where(big, hi, mid)

    top = jnp.maximum(jnp.max(jnp.where(M > 0, z, 0), axis=0), 0)
    lo, hi = jax.lax.fori_loop(0, PROJECTION_ITERS, halve,
                               (jnp.zeros_like(top), top))
    interior = jnp.sum(M * ((z - A) <= lo[None]) * (z > lo[None]), axis=0)
    tau = jnp.where(interior > 0, lo + (g(lo) - c) / jnp.maximum(interior, 1),
                    lo)
    tau = jnp.clip(tau, lo, hi)
    return jnp.where(need[None], jnp.clip(z - tau[None], 0, A) * M, box)


def cast_spec(spec, dtype):
    return {k: (v if k == "kinds" else jnp.asarray(v, dtype))
            for k, v in spec.items()}


# k* (eq. 27) is a near-tie where the runner-up's beta_k sum_r y lies within
# this share of the largest: float32 sums in another order may pick either,
# both are valid subgradients, and the trajectories part from the next slot
TIE = 1e-5


def near_tie(bs, x):
    """Whether some port with arrivals has a near-tie for k*; bs (L, K)."""
    top = jnp.sort(bs, axis=1)
    first, second = top[:, -1], top[:, -2]
    return jnp.any((x > 0) & (first > 0) & (first - second <= TIE * first))


def oga(spec, arrivals, eta0, decay, dtype=jnp.float32):
    """OGASched from y(1) = 0. Returns (rewards (T,), final y (L, R, K),
    chips (T, L), tied (T,)): chips[t, l] = sum_r y_(l, r, 0) after slot
    t's update; tied[t] says k* was a near-tie in slot t's update, so that
    rewards from slot t + 1 on depend on which subgradient was taken."""
    spec = cast_spec(spec, dtype)
    m = spec["mask"][:, :, None]
    L, R = spec["mask"].shape
    K = spec["a"].shape[1]

    def slot(carry, x):
        y, eta = carry
        q = reward(spec, x, y)
        ym = y * m
        bs = spec["beta"][None] * jnp.sum(ym, axis=1)
        kstar = jnp.argmax(bs, axis=1)
        grad = utility_grad(spec["kinds"], spec["alpha"][None], ym) \
            - spec["beta"][None, None] * jax.nn.one_hot(kstar, K,
                                                        dtype=dtype)[:, None]
        y = project(y + eta * x[:, None, None] * grad * m, spec["a"],
                    spec["c"], spec["mask"])
        return (y, eta * decay), (q, jnp.sum(y[:, :, 0], axis=1),
                                  near_tie(bs, x))

    decay = jnp.asarray(decay, dtype)

    y0 = jnp.zeros((L, R, K), dtype)
    (y, _), (q, chips, tied) = jax.lax.scan(
        slot, (y0, jnp.asarray(eta0, dtype)), jnp.asarray(arrivals, dtype))
    return q.astype(jnp.float32), y, chips.astype(jnp.float32), tied


# heuristics ---------------------------------------------------------------

# requested parallelism, as a share of the ports' reachable instances
PARALLELISM = {"drf": 0.97, "binpacking": 0.95, "spreading": 0.95}
# instance preference by utilization: DRF in index order, bin-packing the
# fullest first, spreading the emptiest first
NODE_SIGN = {"drf": 0.0, "binpacking": 1.0, "spreading": -1.0}
HEURISTICS = ("drf", "fairness", "binpacking", "spreading")


def fairness(spec, x):
    """Each arrived port gets a_l^k / sum_{arrived l' in L_r} a_l'^k of c_r^k,
    capped at a_l^k."""
    m = spec["mask"] * x[:, None]
    want = m[:, :, None] * spec["a"][:, None, :]
    total = jnp.sum(want, axis=0, keepdims=True)
    share = jnp.where(total > 0, want / jnp.maximum(total, 1e-9), 0)
    return jnp.minimum(share * spec["c"][None], spec["a"][:, None, :]) \
        * m[:, :, None]


def budgeted(spec, x, name):
    """Ports in turn take min(a_l^k, what is left) from their reachable
    instances in preference order, up to ceil(share * |R_l|) instances'
    worth of a_l^k per resource."""
    mask, a, c = spec["mask"], spec["a"], spec["c"]
    L, R = mask.shape
    w = jnp.ceil(PARALLELISM[name] * jnp.sum(mask, axis=1))
    if name == "drf":
        reach = jnp.sum(mask[:, :, None] * c[None], axis=1)
        key = jnp.max(a / jnp.maximum(reach, 1e-9), axis=1)
    else:
        key = jnp.arange(L, dtype=a.dtype)
    ports = jnp.argsort(jnp.where(x > 0, key, _BIG), stable=True)
    r_idx = jnp.arange(R, dtype=a.dtype)

    def port(i, carry):
        y, rem = carry
        l = ports[i]
        used = jnp.mean((c - rem) / jnp.maximum(c, 1e-9), axis=1)
        pref = NODE_SIGN[name] * used - 1e-6 * r_idx
        pref = jnp.where(mask[l] > 0, pref, -_BIG)
        order = jnp.argsort(-pref, stable=True)
        take = jnp.minimum(a[l][None], rem[order]) * mask[l][order][:, None]
        before = jnp.cumsum(take, axis=0) - take
        got = jnp.clip(w[l] * a[l][None] - before, 0, take) * x[l]
        got = jnp.zeros_like(got).at[order].set(got)
        return y.at[l].add(got), rem - got

    y0 = jnp.zeros((L, R, a.shape[1]), a.dtype)
    y, _ = jax.lax.fori_loop(0, L, port, (y0, c))
    return y


def heuristic(spec, arrivals, name, dtype=jnp.float32):
    """(T,) per-slot rewards of one heuristic; every slot starts from full
    capacity."""
    spec = cast_spec(spec, dtype)

    def slot(_, x):
        y = fairness(spec, x) if name == "fairness" else budgeted(spec, x, name)
        return None, reward(spec, x, y)

    _, q = jax.lax.scan(slot, None, jnp.asarray(arrivals, dtype))
    return q.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("algorithms", "dtype"))
def rewards_batch(spec, arrivals, eta0, decay, algorithms, dtype=jnp.float32):
    """({algorithm: (G, T) rewards}, (G, T) OGASched's near-ties) over
    stacked deployments."""
    out, tied = {}, None
    for name in algorithms:
        if name == "ogasched":
            q, _, _, tied = jax.vmap(
                lambda s, x, e, d: oga(s, x, e, d, dtype))(
                    spec, arrivals, eta0, decay)
            out[name] = q
        else:
            out[name] = jax.vmap(
                lambda s, x: heuristic(s, x, name, dtype))(spec, arrivals)
    return out, tied


oga_jit = jax.jit(oga, static_argnames=("dtype",))


def improvement_pct(oga_avg, base_avg):
    """OGASched's gain over a baseline in percent of the baseline's magnitude."""
    oga_avg = np.asarray(oga_avg, np.float64)
    base_avg = np.asarray(base_avg, np.float64)
    return 100.0 * (oga_avg - base_avg) / np.maximum(np.abs(base_avg), 1e-9)


def pow2_grant(chips: np.ndarray) -> np.ndarray:
    """Largest power of two not above floor(chips); 0 below one chip."""
    g = np.floor(np.maximum(chips, 0)).astype(np.int64)
    out = np.zeros_like(g)
    pos = g > 0
    out[pos] = 1 << (np.floor(np.log2(g[pos])).astype(np.int64))
    return out
