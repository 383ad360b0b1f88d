"""Plain reference of the job lifecycle, written apart from the program.

Nothing here imports the program. It builds on ``reference.py`` (trace
synthesis, utilities, the exact projection, the heuristics' rules, ``TIE``)
and adds:

* the job-size stream (Lomax, ``works``) and the capacity-fault stream
  (``faults``, failure events only), for the host path (numpy
  ``SeedSequence`` streams) and the device path (``jax.random`` keys folded
  per stream), rebuilt from each deployment's seed;
* the lifecycle of held jobs, slot by slot (``run``):

  1. faults: the slot's capacity is ``c_t = c * f_t``. In-service jobs are
     ranked by remaining work, least first, index breaking ties (SRPT);
     the longest prefix whose summed holdings fit ``c_t`` up to the slack
     ``FEAS_TOL * (1 + c_t)`` on every instance and resource keeps running
     and the rest are evicted. An evicted job that has been evicted at
     most ``max_retries`` times in all goes back to the tail of its port's
     queue, if there is room, with its remaining work (``preserve_work``)
     or its whole work, ready again ``min(base * 2**(n - 1), cap)`` slots
     later at its n-th eviction; otherwise it is dropped, and the progress
     it made is wasted (as is a restarted job's).
  2. arrivals join their port's queue (depth ``queue_depth``), or are
     dropped when it is full;
  3. an idle port admits its queue's head once the head is ready; the head
     blocks the queue behind it;
  4. admitted jobs are allocated against the residual capacity, ``c_t``
     less what in-service jobs hold: OGASched proposes its decision,
     a heuristic its step on the residual capacities, and the proposal of
     each admitted port is projected exactly onto the residual set. A job
     keeps its allocation, and the service rate it gives (the reward of
     eq. 7 without the arrival factor), for its whole tenure; the slot's
     reward is the admitted jobs' rates;
  5. every job in service drains ``max(rate, rate_floor)`` work and
     departs, freeing what it holds, once none is left;
  6. OGASched ascends on the slot's arrivals (not its admissions) from the
     nominal capacities, as in slot mode, starting from a random feasible
     decision drawn from ``PRNGKey(0)``.

* each slot's near-ties: discrete decisions whose margin lies within
  ``reference.TIE`` (relative), which float32 arithmetic in another order
  may take either way, so that what follows is compared only up to them.

Float32 is the reference, bfloat16 the control that ``correct`` must
reject: ``run(..., dtype=jnp.bfloat16)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference

# a prefix of in-service jobs fits c_t up to FEAS_TOL * (1 + c_t)
FEAS_TOL = 1e-4
# a job's work is at least this, so that it holds its port for a slot
WORK_FLOOR = 1e-6
# the fault families this reference synthesizes: failure events
FAULT_KEYS = ("fail_rate", "fail_frac", "repair_mean")
_BIG = 1e30
_TINY = float(np.finfo(np.float32).tiny)

# job sizes and faults ------------------------------------------------------


def _failures(tc: dict) -> tuple[float, float, float]:
    extra = set(tc["faults"]) - set(FAULT_KEYS)
    if extra:
        raise ValueError(f"the lifecycle reference synthesizes failure "
                         f"events only, not {sorted(extra)}")
    return tuple(float(tc["faults"][k]) for k in FAULT_KEYS)


def _lomax_scale(tc: dict) -> float:
    return tc["work_mean"] * (tc["work_tail"] - 1.0) / tc["work_tail"]


def _live(starts, dur, xp):
    """(T, K) how many failures cover each slot: a failure that starts at s
    and lasts d slots covers s, ..., min(s + d, T) - 1."""
    T = starts.shape[0]
    s = xp.arange(T)[:, None, None]
    t = xp.arange(T)[None, :, None]
    covers = (s <= t) & (t < s + dur[:, None, :]) & starts[:, None, :]
    return xp.sum(covers.astype(xp.float32), axis=0)


def host_works(tc: dict) -> np.ndarray:
    """(T, L) job sizes: ``scale * (1 + Pareto(tail))`` with the scale
    that makes the mean ``work_mean``."""
    rng = reference.stream_rng(tc["seed"], "works")
    w = _lomax_scale(tc) * (1.0 + rng.pareto(tc["work_tail"],
                                             size=(tc["T"], tc["L"])))
    return w.astype(np.float32)


def host_faults(tc: dict) -> np.ndarray:
    """(T, K) capacity multipliers: a failure starts on a resource with
    probability ``fail_rate`` a slot, lasts a geometric number of slots of
    mean ``repair_mean`` and removes ``fail_frac`` of the capacity while it
    lasts; d failures at once leave ``(1 - fail_frac)**d``."""
    rate, frac, repair = _failures(tc)
    T, K = tc["T"], tc["K"]
    if rate <= 0:
        return np.ones((T, K), np.float32)
    rng = reference.stream_rng(tc["seed"], "faults")
    starts = rng.uniform(size=(T, K)) < rate
    dur = rng.geometric(1.0 / max(repair, 1.0), size=(T, K))
    live = _live(starts, dur, np).astype(np.float64)
    return np.clip((1.0 - frac) ** live, 0.0, 1.0).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _device_generator(T, L, K, scale, tail, rate, frac, repair):
    def one(seed):
        u = jax.random.uniform(reference._stream_key(seed, "works"), (T, L),
                               minval=_TINY, maxval=1.0)
        pareto = u ** (-1.0 / tail) - 1.0
        works = (scale * (1.0 + pareto)).astype(jnp.float32)
        if rate <= 0:
            return works, jnp.ones((T, K), jnp.float32)
        k_start, k_dur, _, _ = jax.random.split(
            reference._stream_key(seed, "faults"), 4)
        starts = jax.random.uniform(k_start, (T, K)) < rate
        u = jax.random.uniform(k_dur, (T, K), minval=_TINY, maxval=1.0)
        p = 1.0 / max(repair, 1.0)
        dur = jnp.maximum(jnp.ceil(jnp.log(u) / jnp.log1p(-p)),
                          1.0).astype(jnp.int32)
        mult = (1.0 - frac) ** _live(starts, dur, jnp)
        return works, jnp.clip(mult, 0.0, 1.0)

    return jax.jit(jax.vmap(one))


def device_streams(tcs: list[dict]):
    """Stacked (works, faults) of deployments that share every static
    parameter, drawn on the default device from each one's seed."""
    t0 = tcs[0]
    gen = _device_generator(t0["T"], t0["L"], t0["K"], _lomax_scale(t0),
                            float(t0["work_tail"]), *_failures(t0))
    return gen(jnp.asarray([t["seed"] for t in tcs], jnp.uint32))


def traces(tcs: list[dict], templates: dict, device: bool):
    """(spec, arrivals, works, faults) stacked over deployments, from the
    device path or the host path."""
    if device:
        spec, x = reference.device_traces(tcs, templates)
        works, faults = device_streams(tcs)
        spec = {k: np.asarray(v) for k, v in spec.items()}
        return spec, np.asarray(x), np.asarray(works), np.asarray(faults)
    specs = [reference.host_spec(t, templates) for t in tcs]
    spec = {k: np.stack([s[k] for s in specs]) for k in specs[0]}
    return (spec, np.stack([reference.host_arrivals(t) for t in tcs]),
            np.stack([host_works(t) for t in tcs]),
            np.stack([host_faults(t) for t in tcs]))


# one slot's pieces ---------------------------------------------------------


def gain_penalty(spec, y):
    """Per port: the speedup utility of the allocation y (L, R, K) and its
    communication penalty (eq. 7 without the arrival factor)."""
    m = spec["mask"][:, :, None]
    ym = y * m
    gain = jnp.sum(reference.utility(spec["kinds"], spec["alpha"][None], ym)
                   * m, axis=(1, 2))
    penalty = jnp.max(spec["beta"][None] * jnp.sum(ym, axis=1), axis=1)
    return gain, penalty


def first_feasible(spec, key):
    """OGASched's first decision: uniform draws times a_l^k on the edges,
    each instance's column scaled down to its capacity."""
    L, R = spec["mask"].shape
    K = spec["a"].shape[1]
    u = jax.random.uniform(key, (L, R, K), jnp.float32)
    y = u * spec["a"][:, None, :] * spec["mask"][:, :, None]
    over = spec["c"] / jnp.maximum(jnp.sum(y, axis=0), 1e-9)
    return y * jnp.minimum(over, 1.0)[None]


def budgeted(spec, x, name, soft=None, full=None):
    """A budgeted heuristic's step (DRF, bin-packing, spreading; the rules
    of ``reference.budgeted``): (allocation, near-tie, shaky), shaky (L,
    R, K) marking the granted cells whose amounts rest on rounding that
    another order of float32 operations may take otherwise: a part of a
    cell at a port's budget boundary (a cumulative sum's rounding), and
    the remainder of a cell whose residual ``soft`` (R, K) marks as resting
    on such rounding already.

    DRF's ports are a near-tie where their dominant shares lie within TIE
    of each other. A port's order of instances is a near-tie where two
    instances next to each other in it have scores within reach of each
    other and their swap moves the port's budget boundary. Within reach
    means within TIE, where their utilizations differ, and more where an
    instance has cells that ``full`` (R, K) marks: cells used to within
    rounding of their capacity by holdings that rest on rounding. Such a
    cell's residual is float32 dust of either sign, so that its term of
    the utilization is 0 or, once an earlier port has taken the dust, 1,
    as the rounding falls: each such cell an earlier port reached widens
    the instance's reach by 1/K.
    """
    mask, a, c = spec["mask"], spec["a"], spec["c"]
    L, R = mask.shape
    K = a.shape[1]
    budget = jnp.ceil(reference.PARALLELISM[name] * jnp.sum(mask, axis=1))
    sign = reference.NODE_SIGN[name]
    arrived = x > 0
    tied = jnp.zeros((), bool)
    if name == "drf":
        reach = jnp.sum(mask[:, :, None] * c[None], axis=1)
        key = jnp.max(a / jnp.maximum(reach, 1e-9), axis=1)
        pair = arrived[:, None] & arrived[None] & ~jnp.eye(L, dtype=bool)
        tied = jnp.any(pair & (jnp.abs(key[:, None] - key[None])
                               <= reference.TIE * jnp.maximum(key[:, None],
                                                              key[None])))
    else:
        key = jnp.arange(L, dtype=a.dtype)
    ports = jnp.argsort(jnp.where(arrived, key, _BIG), stable=True)
    r_idx = jnp.arange(R, dtype=a.dtype)
    none_rk = jnp.zeros((R, K), bool)
    soft = none_rk if soft is None else soft
    full = none_rk if full is None else full

    def port(i, carry):
        y, shaky, rem, soft, tied, reached = carry
        l = ports[i]
        util = jnp.mean((c - rem) / jnp.maximum(c, 1e-9), axis=1)
        score = jnp.where(mask[l] > 0, sign * util - 1e-6 * r_idx, -_BIG)
        order = jnp.argsort(-score, stable=True)
        left = rem[order]
        take = jnp.minimum(a[l][None], left) * mask[l][order][:, None]
        before = jnp.cumsum(take, axis=0) - take
        got = jnp.clip(budget[l] * a[l][None] - before, 0, take) * x[l]
        if sign:
            s, u = score[order], util[order]
            dust = jnp.sum(full & reached, axis=1)[order] / K
            both = (take[:-1] > 0) & (take[1:] > 0)
            whole = (got[:-1] == take[:-1]) & (got[1:] == take[1:])
            none = (got[:-1] == 0) & (got[1:] == 0)
            moves = jnp.any(both & ~whole & ~none, axis=1)
            width = dust[:-1] + dust[1:]
            close = ((u[:-1] != u[1:]) | (width > 0)) & (
                s[:-1] - s[1:] <= reference.TIE + width)
            tied = tied | jnp.any(moves & close)
        # where this port would take the dust of a full cell
        near = (mask[l][order][:, None] > 0) & (a[l][None] > 0) & (
            before < budget[l] * a[l][None]) & arrived[l]
        reached = reached | jnp.zeros_like(near).at[order].set(near)
        cut = (got > 0) & (got < take)
        rest = (got > 0) & (got == left) & soft[order]
        unsorted = lambda v: jnp.zeros_like(v).at[order].set(v)
        shaky = shaky.at[l].set(unsorted(cut | rest))
        soft = (soft & ~unsorted(got == left)) | unsorted(cut)
        got = unsorted(got)
        return y.at[l].add(got), shaky, rem - got, soft, tied, reached

    y0 = jnp.zeros((L, R, K), a.dtype)
    y, shaky, _, _, tied, _ = jax.lax.fori_loop(
        0, L, port, (y0, y0 > 0, c, soft, tied, none_rk))
    return y, tied, shaky


def propose(spec, x, name, soft, full):
    """(allocation, near-tie, shaky cells) of heuristic ``name`` for the
    ports x (see ``budgeted``)."""
    if name == "fairness":
        y = reference.fairness(spec, x)
        return y, jnp.zeros((), bool), (y > 0) & soft[None]
    return budgeted(spec, x, name, soft, full)


def _push(q, at, item):
    """Write ``item`` (L,) into each port's queue (L, Q) at ``at`` (L,
    bool) in the position given by the queue's length."""
    pos = jnp.arange(q.shape[1])[None] == item["len"][:, None]
    return jnp.where(at[:, None] & pos, item["v"][:, None], q)


def _pop(q, at):
    """Drop the head of each queue (L, Q) where ``at``."""
    shifted = jnp.concatenate([q[:, 1:], jnp.zeros_like(q[:, :1])], axis=1)
    return jnp.where(at[:, None], shifted, q)


# the lifecycle --------------------------------------------------------------


def run(spec, arrivals, works, faults, algorithm, eta0, decay, *,
        queue_depth, rate_floor, policy, dtype=jnp.float32):
    """One deployment's lifecycle under one algorithm. ``policy`` holds
    ``backoff_base``, ``backoff_cap``, ``max_retries`` and
    ``preserve_work``. Returns {event: (T, ...)}: ``rewards``,
    ``admitted``, ``departed``, ``evicted`` (T, L); ``dropped`` and
    ``rdropped``, the queue-full and eviction drops so far; ``used`` (T, R,
    K), what is held after the slot's admissions; ``running``, ``q_depth``
    (T, L) at the slot's end; ``jct`` (T, L) of departures; ``work_done``
    (T, L), ``wasted`` (T,); ``retried`` (T, L), an admission of a job
    evicted before; ``tied`` (T,), a near-tie in a decision of
    the slot itself, and ``tied_next`` (T,), a k* near-tie in OGASched's
    update, which moves only the slots after it."""
    spec = reference.cast_spec(spec, dtype)
    mask, a, c = spec["mask"], spec["a"], spec["c"]
    m = mask[:, :, None]
    L, R = mask.shape
    K = a.shape[1]
    Q = queue_depth
    floor = jnp.asarray(rate_floor, dtype)
    TIE = reference.TIE
    zero_l = jnp.zeros((L,), dtype)
    zero_i = jnp.zeros((L,), jnp.int32)
    zero_q = jnp.zeros((L, Q), dtype)
    zero_qi = jnp.zeros((L, Q), jnp.int32)
    y0 = (first_feasible({k: jnp.asarray(v, jnp.float32)
                          for k, v in spec.items()},
                         jax.random.PRNGKey(0)).astype(dtype)
          if algorithm == "ogasched" else jnp.zeros((L, R, K), dtype))
    state = {
        "held": jnp.zeros((L, R, K), dtype),
        "shaky": jnp.zeros((L, R, K), bool), "rem": zero_l,
        "arr": zero_i, "start": zero_i, "work": zero_l, "retry": zero_i,
        "rate": zero_l,
        "q_work": zero_q, "q_arr": zero_qi, "q_ready": zero_qi,
        "q_retry": zero_qi, "q_len": zero_i,
        "dropped": jnp.zeros((), jnp.int32),
        "rdropped": jnp.zeros((), jnp.int32),
        "y": y0, "eta": jnp.asarray(eta0, dtype),
    }
    queues = ("q_work", "q_arr", "q_ready", "q_retry")

    def slot(s, inp):
        t, x, w, f = inp
        s = dict(s)
        c_t = c * f[None]

        # 1. faults: keep the SRPT prefix that fits, evict the rest
        busy = s["rem"] > 0
        rank = jnp.where(busy, s["rem"], jnp.inf)
        idx = jnp.arange(L)
        ahead = (rank[None] < rank[:, None]) | (
            (rank[None] == rank[:, None]) & (idx[None] <= idx[:, None]))
        cum = jnp.sum(ahead[:, :, None, None] * (s["held"] * m)[None],
                      axis=1)
        top = c_t + FEAS_TOL * (1 + c_t)
        evict = busy & ~jnp.all(cum <= top[None], axis=(1, 2))
        margin = jnp.min((top[None] - cum) / (1 + c_t)[None], axis=(1, 2))
        tied = jnp.any(busy & (jnp.abs(margin) <= TIE))
        # the order matters only where the whole set does not fit
        near = (jnp.abs(rank[None] - rank[:, None])
                <= TIE * jnp.maximum(rank[None], rank[:, None]))
        pairs = busy[None] & busy[:, None] & (idx[None] != idx[:, None])
        tied |= jnp.any(evict) & jnp.any(pairs & near)
        n = s["retry"] + 1
        back = evict & (n <= policy["max_retries"]) & (s["q_len"] < Q)
        lost = evict & ~back
        delay = jnp.minimum(policy["backoff_base"] * 2.0 ** (n - 1),
                            policy["backoff_cap"]).astype(jnp.int32)
        again = {"q_work": (jnp.maximum(s["rem"], WORK_FLOOR)
                            if policy["preserve_work"] else s["work"]),
                 "q_arr": s["arr"], "q_ready": t + delay, "q_retry": n}
        for q in queues:
            s[q] = _push(s[q], back, {"v": again[q], "len": s["q_len"]})
        s["q_len"] = s["q_len"] + back
        s["rdropped"] = s["rdropped"] + jnp.sum(lost)
        progress = jnp.maximum(s["work"] - s["rem"], 0)
        wasted = jnp.sum(progress * (lost if policy["preserve_work"]
                                     else evict))
        s["held"] = jnp.where(evict[:, None, None], 0, s["held"])
        s["rem"] = jnp.where(evict, 0, s["rem"])

        # 2. arrivals join their queue, or are dropped when it is full
        come = x > 0
        room_q = s["q_len"] < Q
        fresh = {"q_work": w, "q_arr": jnp.full((L,), t),
                 "q_ready": jnp.full((L,), t), "q_retry": zero_i}
        for q in queues:
            s[q] = _push(s[q], come & room_q,
                         {"v": fresh[q], "len": s["q_len"]})
        s["q_len"] = s["q_len"] + (come & room_q)
        s["dropped"] = s["dropped"] + jnp.sum(come & ~room_q)

        # 3. idle ports admit a ready head
        admit = (s["rem"] <= 0) & (s["q_len"] > 0) & (
            s["q_ready"][:, 0] <= t)
        head = {q: s[q][:, 0] for q in queues}
        for q in queues:
            s[q] = _pop(s[q], admit)
        s["q_len"] = s["q_len"] - admit
        admit_x = admit.astype(dtype)

        # 4. allocate the admitted jobs against the residual capacity
        free = c_t - jnp.sum(s["held"] * m, axis=0)
        c_res = jnp.maximum(free, 0)
        if algorithm == "ogasched":
            prop = s["y"]
        else:
            # residuals that rest on rounding, and the full ones among them
            soft = jnp.any(s["shaky"] & (s["held"] > 0), axis=0)
            full = soft & (jnp.abs(free) <= TIE * (1 + c_t))
            prop, tie, part = propose(dict(spec, c=c_res), admit_x,
                                      algorithm, soft, full)
            tied |= tie
        z = prop * admit_x[:, None, None]
        alloc = reference.project(z, a, c_res, mask)
        # cells the projection moved: rounding there is the method's own
        cut = (jnp.sum(jnp.clip(z, 0, a[:, None]) * m, axis=0) > c_res)
        shaky_new = cut[None] | (part if algorithm != "ogasched" else True)
        s["shaky"] = jnp.where(admit[:, None, None], shaky_new, s["shaky"])
        gain, penalty = gain_penalty(spec, alloc)
        rate = gain - penalty
        tied |= jnp.any(admit & (jnp.abs(rate - floor) <= TIE * jnp.maximum(
            jnp.abs(gain) + jnp.abs(penalty), floor)))
        reward_t = jnp.sum(admit_x * rate)
        s["held"] = jnp.where(admit[:, None, None], alloc, s["held"])
        s["rate"] = jnp.where(admit, rate, s["rate"])
        work = jnp.maximum(head["q_work"], WORK_FLOOR)
        s["rem"] = jnp.where(admit, work, s["rem"])
        s["work"] = jnp.where(admit, work, s["work"])
        s["arr"] = jnp.where(admit, head["q_arr"], s["arr"])
        s["retry"] = jnp.where(admit, head["q_retry"], s["retry"])
        s["start"] = jnp.where(admit, t, s["start"])
        used = jnp.sum(s["held"] * m, axis=0)

        # 5. serve, and release the jobs that are done
        busy = s["rem"] > 0
        speed = jnp.maximum(s["rate"], floor)
        left = s["rem"] - speed * busy
        done = jnp.minimum(speed, s["rem"]) * busy
        depart = busy & (left <= 0)
        tied |= jnp.any(busy & (jnp.abs(left) <= TIE * s["work"]))
        jct = (t - s["arr"] + 1).astype(dtype) * depart
        s["held"] = jnp.where(depart[:, None, None], 0, s["held"])
        s["rem"] = jnp.where(depart, 0, jnp.maximum(left, 0))

        # 6. OGASched ascends on the arrivals, from the nominal capacities
        tied_next = jnp.zeros((), bool)
        if algorithm == "ogasched":
            y = s["y"]
            ym = y * m
            spread = spec["beta"][None] * jnp.sum(ym, axis=1)
            kstar = jax.nn.one_hot(jnp.argmax(spread, axis=1), K, dtype=dtype)
            grad = reference.utility_grad(spec["kinds"], spec["alpha"][None],
                                          ym) - spec["beta"] * kstar[:, None]
            s["y"] = reference.project(
                y + s["eta"] * x.astype(dtype)[:, None, None] * grad * m,
                a, c, mask)
            tied_next = reference.near_tie(spread, x)
        s["eta"] = s["eta"] * jnp.asarray(decay, dtype)
        out = {"rewards": reward_t, "admitted": admit, "departed": depart,
               "evicted": evict, "dropped": s["dropped"],
               "rdropped": s["rdropped"], "used": used,
               "running": s["rem"] > 0, "q_depth": s["q_len"], "jct": jct,
               "work_done": done, "wasted": wasted,
               "retried": admit & (head["q_retry"] > 0), "tied": tied,
               "tied_next": tied_next}
        return s, out

    T = arrivals.shape[0]
    inputs = (jnp.arange(T, dtype=jnp.int32), jnp.asarray(arrivals, dtype),
              jnp.asarray(works, dtype), jnp.asarray(faults, dtype))
    _, out = jax.lax.scan(slot, state, inputs)
    return out


@functools.partial(jax.jit, static_argnames=(
    "algorithm", "queue_depth", "rate_floor", "policy", "dtype"))
def run_batch(spec, arrivals, works, faults, eta0, decay, *, algorithm,
              queue_depth, rate_floor, policy, dtype=jnp.float32):
    """``run`` over stacked deployments; ``policy`` a tuple of (name,
    value) pairs, as a static argument must be hashable."""
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda s, x, w, f, e, d: run(
            s, x, w, f, algorithm, e, d, queue_depth=queue_depth,
            rate_floor=rate_floor, policy=dict(policy), dtype=dtype))(
                spec, arrivals, works, faults, eta0, decay)


def untied(out) -> np.ndarray:
    """(G,) slots compared a deployment: those before the first slot with a
    near-tie of its own, or after one of OGASched's k* near-ties."""
    tied = np.asarray(out["tied"])
    nxt = np.asarray(out["tied_next"])
    T = tied.shape[1]
    first = np.where(tied.any(axis=1), np.argmax(tied, axis=1), T)
    after = np.where(nxt.any(axis=1), np.argmax(nxt, axis=1) + 1, T)
    return np.minimum(first, after)
