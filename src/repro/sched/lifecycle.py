"""Occupancy-aware job-lifecycle simulation layer.

The paper's jobs "request multiple computing resources and hold onto them
during their execution", but the slot-mode simulator (sched.simulator)
recomputes allocations from full capacity every slot: nothing is ever
occupied, completed, or released. This module adds the missing lifecycle —
jobs that arrive with a sampled amount of work, receive an allocation,
*hold* it while executing, and depart when their work drains — as one pure
``lax.scan``, so it jit-compiles, vmaps over scenario grids (sched.sweep),
and composes with both OGA backends (kernels.ops).

State machine per port (one job in service per port, FIFO queue behind it):

    arrival --push--> QUEUED --admit (port idle)--> RUNNING --drain--> DONE
        +--queue full--> DROPPED      RUNNING --evict--> QUEUED (backoff)
                                         +--retry budget spent--> DROPPED

Slot order (one ``_step``): apply the slot's fault multiplier (effective
capacity ``c_t = c * f_t``) and evict the marginal in-service jobs that no
longer fit (see ``_evict`` for the documented, jit-safe rule; evictions
re-queue with capped exponential backoff and a bounded retry budget) ->
enqueue arrivals -> admit *ready* queue heads on idle ports -> allocate
against the *surviving residual* capacity (graph.residual_capacity against
``c_t``) -> collect admission reward -> service all running jobs at their
utility-derived rate (reward.service_rates on the held allocation) ->
depart drained jobs, freeing capacity -> policy update (OGA ascent on the
admitted indicator). Without a fault stream (``faults=None``) the fault
blocks are skipped entirely and every slot reduces bitwise to the
pre-fault semantics (tests/test_lifecycle_faults.py pins an all-ones
fault stream against ``faults=None`` as well).

The allocation a job receives is the policy's proposal projected onto the
residual-capacity polytope, so ``held + newly-allocated <= c`` holds by
construction at every slot. When every job's work is ~0 (duration = 1 slot)
queues never form, the residual equals the full capacity, and the per-slot
rewards reduce exactly to slot-mode ``ogasched.run`` / ``baselines.run``
(tests/test_lifecycle.py pins this).

Metrics: per-job JCT (slots from arrival to departure, queueing included)
and slowdown (JCT / service slots) as compared in heSRPT (arXiv:1903.09346),
plus per-resource utilization as in online ML-cluster scheduling
(arXiv:1801.00936). ``summarize`` reduces a trace to scalars.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import baselines, graph, projection, reward
from repro.core.graph import ClusterSpec
from repro.kernels import ops

# Default pool (heuristics only — sweep/golden defaults are keyed on these).
ALGORITHMS = ("ogasched",) + baselines.BASELINES
# Everything runnable here, including the size/speedup-aware optimal
# policies. HESRPT runs in "residual work exposed" mode: each slot the
# policy ranks the admitted jobs against every in-service job's *remaining*
# work (state.remaining), the exact information the heSRPT optimality proof
# assumes (arXiv:1903.09346).
ALL_ALGORITHMS = ("ogasched",) + baselines.ALL_BASELINES

# Jobs with sampled work below this floor still occupy their port for one
# slot (duration-1 jobs are the slot-mode reduction, not zero-duration).
WORK_FLOOR = 1e-6

# Feasibility slack of the eviction rule: an in-service prefix "fits" the
# surviving capacity up to this absolute + relative tolerance, so float
# accumulation over long scans (held sums reassociated by the prefix
# einsum) can never evict a job a genuine capacity drop would have kept —
# real fault events remove >= a few percent of c, orders of magnitude
# above this slack.
FEAS_TOL = 1e-4


@dataclasses.dataclass(frozen=True)
class FaultPolicy:
    """How the lifecycle reacts to capacity loss (jit-static, hashable).

    backoff_base:  re-queue delay of a job's FIRST retry, in slots; retry
                   n waits ``min(backoff_base * 2**(n-1), backoff_cap)``
                   (capped exponential backoff).
    backoff_cap:   upper bound of the backoff delay, in slots.
    max_retries:   evictions a job survives; the (max_retries+1)-th
                   eviction drops it (counted in ``rdropped``).
    preserve_work: True re-queues the job with its *remaining* work
                   (checkpointed progress); False restarts it from its full
                   size, counting the lost progress as wasted work.
    """

    backoff_base: float = 2.0
    backoff_cap: float = 64.0
    max_retries: int = 3
    preserve_work: bool = True


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LifecycleState:
    """Pure scan carry — every leaf is a fixed-shape jnp array.

    held:      (L, R, K) resources granted to in-service jobs.
    remaining: (L,) work left for the in-service job; 0 <=> port idle.
    svc_arr:   (L,) arrival slot of the in-service job (JCT anchor).
    svc_start: (L,) admission slot of the in-service job (slowdown anchor).
    svc_work:  (L,) total work of the in-service job (restart/wasted-work
               anchor under evictions).
    svc_retry: (L,) evictions the in-service job has survived so far.
    svc_rate:  (L,) service rate of the in-service job's held allocation,
               fixed at admission (held does not change during a tenure).
    q_work:    (L, Q) FIFO of queued job sizes (0-padded past q_len).
    q_arr:     (L, Q) FIFO of queued arrival slots.
    q_ready:   (L, Q) FIFO of earliest-admission slots (backoff gates).
    q_retry:   (L, Q) FIFO of per-job eviction counts.
    q_len:     (L,) queue occupancy.
    dropped:   () cumulative arrivals rejected by a full queue.
    rdropped:  () cumulative evicted jobs dropped (retry budget spent or
               re-queue refused by a full queue).
    y:         (L, R, K) OGA decision (unused zeros for heuristics).
    eta:       () OGA learning rate (decayed per slot, as in slot mode).
    t:         () slot counter.
    """

    held: jax.Array
    remaining: jax.Array
    svc_arr: jax.Array
    svc_start: jax.Array
    svc_work: jax.Array
    svc_retry: jax.Array
    svc_rate: jax.Array
    q_work: jax.Array
    q_arr: jax.Array
    q_ready: jax.Array
    q_retry: jax.Array
    q_len: jax.Array
    dropped: jax.Array
    rdropped: jax.Array
    y: jax.Array
    eta: jax.Array
    t: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LifecycleTrace:
    """Per-slot event record (leaves (T, ...); (G, T, ...) from run_grid).

    rewards:   (T,) admission reward q(admitted, alloc) per slot.
    admitted:  (T, L) job entered service this slot.
    departed:  (T, L) job drained and freed its resources this slot.
    jct:       (T, L) completion time in slots (arrival -> departure,
               queueing included); valid where ``departed``.
    svc_slots: (T, L) service time in slots (admission -> departure);
               valid where ``departed``. slowdown = jct / svc_slots.
    used:      (T, R, K) peak occupancy of the slot: held + newly allocated,
               before departures free anything.
    running:   (T, L) port busy at the end of the slot.
    q_depth:   (T, L) queue occupancy at the end of the slot.
    dropped:   (T,) cumulative queue-full rejections.
    evicted:   (T, L) in-service job evicted by a capacity drop this slot.
    wasted:    (T,) work units of progress discarded this slot (evicted
               jobs that were dropped, or re-queued under restart-from-zero).
    rdropped:  (T,) cumulative evicted-job drops (retry budget / full queue).
    work_done: (T, L) work units drained this slot (goodput numerator).
    """

    rewards: jax.Array
    admitted: jax.Array
    departed: jax.Array
    jct: jax.Array
    svc_slots: jax.Array
    used: jax.Array
    running: jax.Array
    q_depth: jax.Array
    dropped: jax.Array
    evicted: jax.Array
    wasted: jax.Array
    rdropped: jax.Array
    work_done: jax.Array


def init_state(
    spec: ClusterSpec,
    eta0: float | jax.Array,
    queue_depth: int,
    y0: Optional[jax.Array] = None,
) -> LifecycleState:
    L, R, K = spec.L, spec.R, spec.K
    dtype = spec.a.dtype
    return LifecycleState(
        held=jnp.zeros((L, R, K), dtype),
        remaining=jnp.zeros((L,), dtype),
        svc_arr=jnp.zeros((L,), jnp.int32),
        svc_start=jnp.zeros((L,), jnp.int32),
        svc_work=jnp.zeros((L,), dtype),
        svc_retry=jnp.zeros((L,), jnp.int32),
        svc_rate=jnp.zeros((L,), dtype),
        q_work=jnp.zeros((L, queue_depth), dtype),
        q_arr=jnp.zeros((L, queue_depth), jnp.int32),
        q_ready=jnp.zeros((L, queue_depth), jnp.int32),
        q_retry=jnp.zeros((L, queue_depth), jnp.int32),
        q_len=jnp.zeros((L,), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
        rdropped=jnp.zeros((), jnp.int32),
        y=graph.zeros_like_decision(spec) if y0 is None else y0,
        eta=jnp.asarray(eta0, dtype),
        t=jnp.zeros((), jnp.int32),
    )


def _evict(
    spec: ClusterSpec,
    state: LifecycleState,
    c_t: jax.Array,
    t: jax.Array,
    policy: FaultPolicy,
    queue_depth: int,
):
    """Evict the marginal in-service jobs that no longer fit ``c_t``.

    The documented, jit-safe rule: rank in-service jobs by ascending
    remaining work (stable, index tiebreak — the SRPT order, so the jobs
    closest to completion are kept and expected wasted work is minimised)
    and keep the maximal prefix whose cumulative held allocation fits the
    surviving capacity elementwise, within FEAS_TOL slack. Usage is
    non-negative, so the cumulative sums are monotone in rank and the kept
    set is a genuine prefix. The ranking is the sort-free O(L^2) pairwise
    comparison (cf. baselines._rank_order) — no sort primitive enters the
    scan body (the PR 3 shard_map miscompile class).

    Evicted jobs re-queue at their own port's tail with retry count n+1,
    earliest-admission slot ``t + min(backoff_base * 2**n, backoff_cap)``
    (capped exponential backoff), and either their remaining work
    (``policy.preserve_work``) or their full size (restart-from-zero).
    Jobs whose retry budget is spent — or whose queue is full — are
    dropped (``rdropped``); their drained progress counts as wasted work,
    as does the progress of every restart-from-zero re-queue.
    """
    L = spec.L
    dtype = spec.a.dtype
    in_svc = state.remaining > 0
    idx = jnp.arange(L)
    rem_key = jnp.where(in_svc, state.remaining, jnp.inf)
    before_eq = (
        (rem_key[None, :] < rem_key[:, None])
        | ((rem_key[None, :] == rem_key[:, None])
           & (idx[None, :] <= idx[:, None]))
    )  # (L, L): job j at or before job l in the keep order
    held_m = state.held * spec.mask[:, :, None]
    # (L, R, K) cumulative usage of the rank-<=l prefix. HIGHEST: at the
    # TPU's default matmul precision the held f32 values would be summed as
    # bf16, an error far past FEAS_TOL that evicts jobs no fault displaced.
    cum = jnp.einsum(
        "lj,jrk->lrk", before_eq.astype(dtype), held_m,
        precision=jax.lax.Precision.HIGHEST,
    )
    slack = FEAS_TOL * (1.0 + c_t)
    fits = jnp.all(cum <= (c_t + slack)[None], axis=(1, 2))
    evict = in_svc & ~fits

    progress = jnp.maximum(state.svc_work - state.remaining, 0.0)
    n_retry = state.svc_retry + 1
    exhausted = n_retry > policy.max_retries
    can_rq = evict & ~exhausted & (state.q_len < queue_depth)
    delay = jnp.minimum(
        policy.backoff_base * jnp.exp2((n_retry - 1).astype(dtype)),
        policy.backoff_cap,
    ).astype(jnp.int32)
    w_rq = (
        jnp.maximum(state.remaining, WORK_FLOOR) if policy.preserve_work
        else state.svc_work
    )
    tail_f = jax.nn.one_hot(state.q_len, queue_depth, dtype=dtype)
    tail_i = jax.nn.one_hot(state.q_len, queue_depth, dtype=jnp.int32)
    rq = can_rq[:, None]
    q_work = jnp.where(rq, state.q_work + tail_f * w_rq[:, None],
                       state.q_work)
    q_arr = jnp.where(rq, state.q_arr + tail_i * state.svc_arr[:, None],
                      state.q_arr)
    q_ready = jnp.where(rq, state.q_ready + tail_i * (t + delay)[:, None],
                        state.q_ready)
    q_retry = jnp.where(rq, state.q_retry + tail_i * n_retry[:, None],
                        state.q_retry)
    q_len = state.q_len + can_rq.astype(jnp.int32)
    rq_drop = evict & ~can_rq
    rdropped = state.rdropped + jnp.sum(rq_drop).astype(jnp.int32)
    lost = rq_drop if policy.preserve_work else evict
    wasted_t = jnp.sum(progress * lost.astype(dtype))

    return dataclasses.replace(
        state,
        held=jnp.where(evict[:, None, None], 0.0, state.held),
        remaining=jnp.where(evict, 0.0, state.remaining),
        q_work=q_work, q_arr=q_arr, q_ready=q_ready, q_retry=q_retry,
        q_len=q_len, rdropped=rdropped,
    ), evict, wasted_t


def _step(
    spec: ClusterSpec,
    state: LifecycleState,
    x_t: jax.Array,
    w_t: jax.Array,
    f_t,
    *,
    algorithm: str,
    decay,
    rate_floor,
    backend: str,
    step_w,
    operands,
    fault_policy: FaultPolicy,
):
    """One slot of the lifecycle state machine; returns (state', events)."""
    L = spec.L
    dtype = spec.a.dtype
    queue_depth = state.q_work.shape[1]
    t = state.t

    # -- faults: surviving capacity + eviction of jobs that no longer fit --
    # f_t is None (no fault stream: the pre-fault program, bitwise) or the
    # slot's (K,) capacity multiplier. Size-aware mode is fully malleable
    # (the whole allocation is rebalanced below against c_t every slot), so
    # nothing is "held" across the drop and eviction does not apply.
    if f_t is None:
        c_t = None
        evict = jnp.zeros((L,), bool)
        wasted_t = jnp.zeros((), dtype)
    else:
        with obs.scope("lifecycle.evict"):
            c_t = spec.c * f_t[None, :]
            if algorithm in baselines.SIZE_AWARE:
                evict = jnp.zeros((L,), bool)
                wasted_t = jnp.zeros((), dtype)
            else:
                state, evict, wasted_t = _evict(
                    spec, state, c_t, t, fault_policy, queue_depth
                )

    with obs.scope("lifecycle.queue"):
        # -- enqueue arrivals (x is an indicator: <=1 job/port/slot) --
        arrive = x_t > 0
        can_q = state.q_len < queue_depth
        push = arrive & can_q
        pushf = push.astype(dtype)
        tail = jax.nn.one_hot(state.q_len, queue_depth, dtype=dtype)  # (L, Q)
        q_work = state.q_work + tail * (w_t * pushf)[:, None]
        q_arr = state.q_arr + (tail * pushf[:, None]).astype(jnp.int32) * t
        # arrivals are ready immediately (backoff gates only re-queued jobs)
        # and start with a zero retry count, so q_retry is untouched by a push
        q_ready = state.q_ready + (tail * pushf[:, None]).astype(jnp.int32) * t
        q_retry = state.q_retry
        q_len = state.q_len + push.astype(jnp.int32)
        dropped = state.dropped + jnp.sum(arrive & ~can_q).astype(jnp.int32)

        # -- admit the queue head wherever the port is idle (and, under
        # faults, the head's backoff window has passed — the FIFO head
        # gates the queue) --
        idle = state.remaining <= 0
        admit = idle & (q_len > 0)
        if f_t is not None:
            admit = admit & (q_ready[:, 0] <= t)
        new_work = jnp.maximum(q_work[:, 0], WORK_FLOOR)
        new_arr = q_arr[:, 0]
        new_retry = q_retry[:, 0]
        shift_w = jnp.concatenate(
            [q_work[:, 1:], jnp.zeros((L, 1), dtype)], 1
        )
        shift_a = jnp.concatenate(
            [q_arr[:, 1:], jnp.zeros((L, 1), jnp.int32)], 1
        )
        shift_r = jnp.concatenate(
            [q_ready[:, 1:], jnp.zeros((L, 1), jnp.int32)], 1
        )
        shift_n = jnp.concatenate(
            [q_retry[:, 1:], jnp.zeros((L, 1), jnp.int32)], 1
        )
        q_work = jnp.where(admit[:, None], shift_w, q_work)
        q_arr = jnp.where(admit[:, None], shift_a, q_arr)
        q_ready = jnp.where(admit[:, None], shift_r, q_ready)
        q_retry = jnp.where(admit[:, None], shift_n, q_retry)
        q_len = q_len - admit.astype(jnp.int32)
        admit_f = admit.astype(dtype)

    with obs.scope("lifecycle.allocate"):
        # -- allocate --
        if algorithm in baselines.SIZE_AWARE:
            # Size-aware mode is PREEMPTIVE: heSRPT's optimality proof
            # assumes the allocation is rebalanced whenever the active set
            # changes (arXiv:1903.09346 §3), so each slot the policy
            # re-divides the FULL surviving capacity across every active job
            # — this slot's admissions plus all in-service jobs, whose
            # residual works (state.remaining) are the sizes it ranks on.
            # ``held`` is replaced wholesale; feasibility vs c_t is the
            # policy's own water-fill invariant, so no residual-capacity
            # netting is needed.
            sizes = jnp.where(admit, new_work, state.remaining)
            active_f = (sizes > 0).astype(dtype)
            spec_t = (
                spec if c_t is None else dataclasses.replace(spec, c=c_t)
            )
            with obs.scope(f"heuristic.{algorithm}"):
                held = baselines.step_fn(algorithm)(
                    spec_t, active_f, step_w, sizes=sizes
                )
            # admission reward on the admitted jobs' share, as in the held
            # path
            reward_t = reward.total_reward(
                spec, admit_f, held * admit_f[:, None, None]
            )
            svc_rate = reward.service_rates(spec, held)
        else:
            # Heuristics and OGA hold allocations for a job's whole tenure:
            # allocate the admitted jobs against the *surviving residual*
            # capacity (nominal capacity when no fault stream runs).
            c_res = graph.residual_capacity(spec, state.held, c_t)
            if algorithm == "ogasched":
                y_prop = state.y
            else:
                with obs.scope(f"heuristic.{algorithm}"):
                    y_prop = baselines.step_fn(algorithm)(
                        graph.residual_spec(spec, state.held, c_t), admit_f,
                        step_w,
                    )
            # exact one-sort projection (core.projection): the per-slot
            # allocation used to be a second 64-pass bisection inside the
            # scan.
            alloc = projection.project_sorted(
                y_prop * admit_f[:, None, None], spec.a, c_res, spec.mask
            )
            # a held allocation is fixed for the job's tenure, and so is its
            # service rate: compute it once, at admission, and carry it
            rate_alloc = reward.service_rates(spec, alloc)
            reward_t = jnp.sum(admit_f * rate_alloc)
            held = jnp.where(admit[:, None, None], alloc, state.held)
            svc_rate = jnp.where(admit, rate_alloc, state.svc_rate)
        remaining = jnp.where(admit, new_work, state.remaining)
        svc_arr = jnp.where(admit, new_arr, state.svc_arr)
        svc_start = jnp.where(admit, t, state.svc_start)
        svc_work = jnp.where(admit, new_work, state.svc_work)
        svc_retry = jnp.where(admit, new_retry, state.svc_retry)
        # (R, K) slot peak
        used = jnp.sum(held * spec.mask[:, :, None], axis=0)

    with obs.scope("lifecycle.service"):
        # -- service: drain work at the utility-derived rate of the held
        # allocation --
        in_svc = remaining > 0
        in_svc_f = in_svc.astype(dtype)
        rates = jnp.maximum(svc_rate, rate_floor)
        rem2 = remaining - rates * in_svc_f
        work_done = jnp.minimum(rates, remaining) * in_svc_f
        depart = in_svc & (rem2 <= 0)
        departf = depart.astype(dtype)
        jct = (t - svc_arr + 1).astype(dtype) * departf
        svc_slots = (t - svc_start + 1).astype(dtype) * departf
        held = jnp.where(depart[:, None, None], 0.0, held)
        remaining = jnp.where(depart, 0.0, jnp.maximum(rem2, 0.0))

    # -- policy update: OGA ascends on the raw arrival indicator, exactly as
    # in slot mode — the learner sees the same stream either way; lifecycle
    # only changes which decisions get *executed* (admissions, netted by
    # residual capacity). Queue/occupancy/fault state never leaks into
    # learning: the regret comparator is defined on the nominal polytope.
    if algorithm == "ogasched":
        with obs.scope("oga.update"):
            y_next = ops.oga_update_spec(
                spec, state.y, x_t, state.eta, backend=backend,
                operands=operands,
            )
    else:
        y_next = state.y

    new_state = LifecycleState(
        held=held, remaining=remaining, svc_arr=svc_arr, svc_start=svc_start,
        svc_work=svc_work, svc_retry=svc_retry, svc_rate=svc_rate,
        q_work=q_work, q_arr=q_arr, q_ready=q_ready, q_retry=q_retry,
        q_len=q_len, dropped=dropped, rdropped=state.rdropped,
        y=y_next, eta=state.eta * decay, t=t + 1,
    )
    events = (
        reward_t, admit, depart, jct, svc_slots, used,
        remaining > 0, q_len, dropped,
        evict, wasted_t, state.rdropped, work_done,
    )
    return new_state, events


@partial(
    jax.jit,
    static_argnames=("algorithm", "queue_depth", "backend", "fault_policy"),
)
def run(
    spec: ClusterSpec,
    arrivals: jax.Array,
    works: jax.Array,
    algorithm: str = "ogasched",
    *,
    eta0: float | jax.Array = 25.0,
    decay: float | jax.Array = 0.9999,
    queue_depth: int = 8,
    rate_floor: float | jax.Array = 1e-3,
    backend: str = "auto",
    y0: Optional[jax.Array] = None,
    faults: Optional[jax.Array] = None,
    fault_policy: FaultPolicy = FaultPolicy(),
) -> LifecycleTrace:
    """Run one algorithm through the job lifecycle over a trace.

    Args:
      arrivals: (T, L) arrival indicators (trace.build_arrivals, or a row
                of a device-synthesized batch — sched.trace_device).
      works:    (T, L) sampled job sizes in work units (trace.build_works
                or the ``works`` leaf of a trace batch from either
                backend); works[t, l] is consumed iff a job arrives at
                (t, l). Must match ``arrivals``' shape.
      algorithm: "ogasched" or a baseline name (baselines.ALL_BASELINES;
                 size-aware names consume ``works`` as known job sizes).
      eta0, decay: OGA hyperparameters; traced arrays vmap (sched.sweep).
      queue_depth: per-port FIFO bound; overflowing arrivals are dropped.
      rate_floor: minimum service rate, so zero-allocation admissions still
        drain (no deadlock) — work units per slot.
      backend: OGA update backend, "auto" | "fused" | "reference".
      y0: initial OGA decision. Defaults to a seeded random feasible point
        rather than slot-mode's zeros: an allocation is *held* for the job's
        whole tenure here, and a zero allocation would pin the first job per
        port to the rate floor, blocking the port for the entire trace.
      faults: optional (T, K) capacity-multiplier stream
        (trace.build_faults); slot t executes against ``c * faults[t]``.
        None (the default) compiles the pre-fault program unchanged.
      fault_policy: eviction/retry/backoff knobs (static; only read when
        ``faults`` is given).
    Returns: LifecycleTrace of per-slot events (leaves lead with T).
    """
    if works.shape != arrivals.shape:
        raise ValueError(
            "works must pair 1:1 with arrivals: got works "
            f"{works.shape} vs arrivals {arrivals.shape}"
        )
    if faults is not None and faults.shape != (arrivals.shape[0], spec.K):
        raise ValueError(
            "faults must be a (T, K) capacity-multiplier stream: got "
            f"{faults.shape} vs T={arrivals.shape[0]}, K={spec.K}"
        )
    backend = ops.resolve_oga_backend(backend)
    use_oga = algorithm == "ogasched"
    operands = ops.pack_spec_operands(spec) if use_oga and backend == "fused" else None
    step_w = None if use_oga else baselines.default_parallelism(spec, algorithm)
    if y0 is None and use_oga:
        y0 = graph.random_feasible_decision(spec, jax.random.PRNGKey(0))
    state = init_state(spec, eta0, queue_depth, y0)

    def body(s, xw):
        x_t, w_t = xw[0], xw[1]
        f_t = xw[2] if faults is not None else None
        return _step(
            spec, s, x_t, w_t, f_t, algorithm=algorithm, decay=decay,
            rate_floor=rate_floor, backend=backend,
            step_w=step_w, operands=operands, fault_policy=fault_policy,
        )

    xs = (arrivals, works) if faults is None else (arrivals, works, faults)
    _, events = jax.lax.scan(body, state, xs)
    return LifecycleTrace(*events)


@jax.jit
def _summarize_batch(tr: LifecycleTrace, c: jax.Array) -> dict[str, jax.Array]:
    G, T = tr.rewards.shape
    dtype = tr.jct.dtype
    dep = tr.departed.astype(bool).reshape(G, -1)   # (G, T*L)
    jct = tr.jct.reshape(G, -1)
    svc = tr.svc_slots.reshape(G, -1)
    n = jnp.sum(dep, axis=-1)                       # (G,) departed jobs
    nf = jnp.maximum(n, 1).astype(dtype)
    some = n > 0
    nan = jnp.asarray(jnp.nan, dtype)
    jct_mean = jnp.sum(jnp.where(dep, jct, 0.0), axis=-1) / nf
    slow = jnp.where(dep, jct / jnp.maximum(svc, 1.0), 0.0)
    slow_mean = jnp.sum(slow, axis=-1) / nf
    # p99 over the departed subset, np.percentile's linear interpolation:
    # non-departed entries sort to +inf past the n valid values, and the
    # interpolation index 0.99*(n-1) never reaches them.
    vals = jnp.sort(jnp.where(dep, jct, jnp.inf), axis=-1)
    pos = 0.99 * (nf - 1.0)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.ceil(pos).astype(jnp.int32)
    v_lo = jnp.take_along_axis(vals, lo[:, None], axis=-1)[:, 0]
    v_hi = jnp.take_along_axis(vals, hi[:, None], axis=-1)[:, 0]
    p99 = v_lo + (pos - lo.astype(dtype)) * (v_hi - v_lo)
    util_k = jnp.mean(
        tr.used / jnp.maximum(c, 1e-9)[:, None], axis=(1, 2)
    )  # (G, K)
    # robustness metrics: evictions re-admit jobs, so subtract the
    # re-queue events (evictions minus hard drops) to count each accepted
    # job exactly once; goodput nets the discarded progress out of the
    # drained work (throughput counts completions, goodput counts work).
    evictions = jnp.sum(tr.evicted.astype(dtype), axis=(1, 2))
    fault_drops = tr.rdropped[:, -1].astype(dtype)
    wasted = jnp.sum(tr.wasted, axis=-1)
    done = jnp.sum(tr.work_done, axis=(1, 2))
    out = {
        "completed": n.astype(dtype),
        "arrived": (
            jnp.sum(tr.admitted.astype(dtype), axis=(1, 2))
            + jnp.sum(tr.q_depth[:, -1].astype(dtype), axis=-1)
            - (evictions - fault_drops)
        ),
        "dropped": tr.dropped[:, -1].astype(dtype),
        "throughput": n.astype(dtype) / T,
        "goodput": (done - wasted) / T,
        "wasted_work": wasted,
        "evictions": evictions,
        "fault_drops": fault_drops,
        "jct_mean": jnp.where(some, jct_mean, nan),
        "jct_p99": jnp.where(some, p99, nan),
        "slowdown_mean": jnp.where(some, slow_mean, nan),
        "utilization": jnp.mean(util_k, axis=-1),
    }
    for k in range(util_k.shape[-1]):
        out[f"utilization/{k}"] = util_k[:, k]
    return out


def summarize_batch(
    tr: LifecycleTrace, spec: ClusterSpec
) -> dict[str, jax.Array]:
    """Jitted, batched ``summarize``: every leaf of ``tr`` leads with a grid
    axis (G, T, ...), ``spec`` leaves with (G, ...); returns {metric: (G,)}
    with exactly the scalars ``summarize`` reports per row. One device
    dispatch replaces the G x algorithms Python double loop that reduced
    large lifecycle grids before (tests pin batch == per-row equality)."""
    return _summarize_batch(tr, spec.c)


def summarize(tr: LifecycleTrace, spec: ClusterSpec) -> dict[str, float]:
    """Host-side scalar metrics for one lifecycle trace.

    jct_mean / jct_p99: completion time in slots over finished jobs.
    slowdown_mean: mean JCT / service-time ratio (1.0 = never queued).
    utilization: mean_t mean_{r,k} used / c; utilization/<k>: per resource.
    completed / arrived / dropped: job counts (arrived = admitted+queued
    minus eviction re-admissions, i.e. each accepted job once, drops
    excluded); throughput: completed per slot.
    goodput: (drained work - wasted work) / T; wasted_work: progress
    discarded by evictions; evictions / fault_drops: event counts.
    """
    departed = np.asarray(tr.departed, bool)
    jct = np.asarray(tr.jct)[departed]
    svc = np.asarray(tr.svc_slots)[departed]
    used = np.asarray(tr.used)  # (T, R, K)
    c = np.maximum(np.asarray(spec.c), 1e-9)
    util_k = (used / c[None]).mean(axis=(0, 1))  # (K,)
    evictions = float(np.asarray(tr.evicted).sum())
    fault_drops = float(np.asarray(tr.rdropped)[-1])
    wasted = float(np.asarray(tr.wasted).sum())
    done = float(np.asarray(tr.work_done).sum())
    T = departed.shape[0]
    out = {
        "completed": float(departed.sum()),
        "arrived": float(np.asarray(tr.admitted).sum()
                         + np.asarray(tr.q_depth)[-1].sum())
                   - (evictions - fault_drops),
        "dropped": float(np.asarray(tr.dropped)[-1]),
        "throughput": float(departed.sum()) / T,
        "goodput": (done - wasted) / T,
        "wasted_work": wasted,
        "evictions": evictions,
        "fault_drops": fault_drops,
        "jct_mean": float(jct.mean()) if jct.size else float("nan"),
        "jct_p99": float(np.percentile(jct, 99)) if jct.size else float("nan"),
        "slowdown_mean": (
            float((jct / np.maximum(svc, 1.0)).mean()) if jct.size
            else float("nan")
        ),
        "utilization": float(util_k.mean()),
    }
    for k, u in enumerate(util_k):
        out[f"utilization/{k}"] = float(u)
    return out


def recovery_time(
    rewards,
    faults,
    frac: float = 0.95,
    window: int = 25,
) -> float:
    """Slots from the first fault until reward recovers to ``frac`` of the
    pre-fault level (host-side diagnostic; benchmarks/bench_faults.py).

    The pre-fault level is the mean per-slot reward over the slots strictly
    before the first faulted slot (any resource's multiplier < 1); recovery
    is the first slot >= the fault where the trailing ``window``-slot moving
    average of the reward reaches ``frac`` x that level. Returns 0.0 when
    the stream never faults, +inf when the run never recovers, NaN when the
    fault lands before any pre-fault baseline exists.
    """
    r = np.asarray(rewards, np.float64)
    f = np.asarray(faults)
    faulted = np.nonzero((f < 1.0).any(axis=-1))[0]
    if faulted.size == 0:
        return 0.0
    t0 = int(faulted[0])
    if t0 == 0:
        return float("nan")
    base = r[:t0].mean()
    if base <= 0.0:
        return float("nan")
    # trailing moving average, window clipped at the start of the trace
    cum = np.concatenate([[0.0], np.cumsum(r)])
    lo = np.maximum(np.arange(len(r)) - window + 1, 0)
    avg = (cum[np.arange(len(r)) + 1] - cum[lo]) / (np.arange(len(r)) - lo + 1)
    ok = np.nonzero(avg[t0:] >= frac * base)[0]
    return float(ok[0]) if ok.size else float("inf")
