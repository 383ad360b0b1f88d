"""The tab2_faults.lifecycle cell on the CPU: the lifecycle reference
against the program for every algorithm, the cell end to end at a size a
test can hold, the control and each planted fault reading not correct,
and the cell's metric readers on hand-built traces."""
import dataclasses
import json
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import drive
import harness
import reference_lifecycle as rl
import tinybench
import tracereduce
from repro.sched import lifecycle, trace

WORKLOAD = "tab2_faults.lifecycle"
CONFIG = os.path.join(harness.ROOT, "chipbench", "configs",
                      "tab2_faults.json")
SMALL = {"L": 6, "R": 16, "K": 3, "T": 80}
SEEDS = (0, 1)
POLICY = lifecycle.FaultPolicy(max_retries=3, preserve_work=True)


def _config() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    """Seeded small deployments with faults: the program's host synthesis
    and the reference's, of the same seeds."""
    cfg = dict(_config(), **SMALL)
    fields = {f.name for f in dataclasses.fields(trace.TraceConfig)}
    tcs, prog = [], []
    for seed in SEEDS:
        tc = dict(cfg, seed=seed)
        tcs.append(tc)
        program = {k: tuple(v) if isinstance(v, list) else v
                   for k, v in tc.items() if k in fields and k != "faults"}
        program = trace.TraceConfig(
            **program, faults=trace.FaultConfig(**tc["faults"]))
        spec, x, w = trace.make_lifecycle(program)
        prog.append((spec, x, w, trace.build_faults(program)))
    return tcs, prog, rl.traces(tcs, cfg["templates"], device=False)


def test_the_reference_rebuilds_the_streams_bit_for_bit(small):
    _, prog, (spec, x, works, faults) = small
    for g, (p_spec, p_x, p_w, p_f) in enumerate(prog):
        for k in spec:
            np.testing.assert_array_equal(np.asarray(getattr(p_spec, k)),
                                          spec[k][g])
        np.testing.assert_array_equal(np.asarray(p_x), x[g])
        np.testing.assert_array_equal(np.asarray(p_w), works[g])
        np.testing.assert_array_equal(np.asarray(p_f), faults[g])
    assert (faults < 1).any() and (faults > 0).all()


def _reference(small, name, dtype=jnp.float32):
    _, _, (spec, x, works, faults) = small
    out = rl.run_batch(
        spec, x, works, faults, np.full(len(SEEDS), 25.0, np.float32),
        np.full(len(SEEDS), 0.9999, np.float32), algorithm=name,
        queue_depth=8, rate_floor=1e-3,
        policy=tuple(sorted(dataclasses.asdict(POLICY).items())),
        dtype=dtype)
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("name", drive.ALGORITHMS)
def test_the_reference_agrees_with_the_program(small, name):
    """Events match exactly and rewards to float32 rounding in the untied
    prefix; the faults evict jobs and evicted jobs come back."""
    _, prog, _ = small
    ref = _reference(small, name)
    untied = rl.untied(ref)
    assert np.all(untied >= SMALL["T"] // 2), untied
    for g, (spec, x, w, f) in enumerate(prog):
        tr = lifecycle.run(spec, x, w, name, eta0=25.0, decay=0.9999,
                           queue_depth=8, rate_floor=1e-3, faults=f,
                           fault_policy=POLICY)
        n = untied[g]
        for k in ("admitted", "departed", "evicted", "dropped", "rdropped",
                  "q_depth", "running"):
            np.testing.assert_array_equal(np.asarray(getattr(tr, k))[:n],
                                          ref[k][g][:n], err_msg=k)
        scale = max(np.mean(np.abs(ref["rewards"][g])), 1.0)
        assert np.max(np.abs(np.asarray(tr.rewards)[:n]
                             - ref["rewards"][g][:n])) <= 1e-5 * scale
    assert ref["evicted"].sum() > 0 and ref["retried"].sum() > 0


def _one_slot_trace(reward: float) -> dict:
    """One deployment (L=2, R=1, K=1) over two slots: a job admitted at
    slot 0 departs at slot 1."""
    tr = {k: np.zeros((1, 2, 2)) for k in ("admitted", "departed", "evicted",
                                             "running", "q_depth", "jct",
                                             "work_done")}
    tr.update({k: np.zeros((1, 2)) for k in ("dropped", "rdropped",
                                              "wasted")})
    tr["admitted"][0, 0, 0] = tr["departed"][0, 1, 0] = 1
    tr["jct"][0, 1, 0] = 2
    tr["rewards"] = np.array([[reward, 0.0]])
    tr["used"] = np.zeros((1, 2, 1, 1))
    return tr


@pytest.mark.parametrize("name,gap", [
    ("drf", "lc_slot_gap"), ("ogasched", "lc_slot_gap"),
    ("fairness", "lc_slot_gap"), ("binpacking", "lc_slot_gap_util_order"),
    ("spreading", "lc_slot_gap_util_order")])
def test_each_slot_gap_reads_its_own_algorithms(name, gap):
    """A reward off by 1e-3 in one algorithm's untied prefix reads in that
    algorithm's gap and leaves the other at 0."""
    driver = harness.load_module(os.path.join(
        harness.ROOT, "chipbench", "drivers", "lifecycle.py"))
    spec = {"c": np.ones((1, 1, 1))}
    want = {n: _one_slot_trace(2.0) for n in drive.ALGORITHMS}
    got = {n: _one_slot_trace(2.0 + (1e-3 if n == name else 0.0))
           for n in drive.ALGORITHMS}
    summary = {n: {k: np.array([v]) for k, v in driver.plain_summary(
        {e: a[0] for e, a in tr.items()}, spec["c"][0]).items()}
        for n, tr in got.items()}
    untied = {n: np.array([2]) for n in drive.ALGORITHMS}
    out = driver.gaps(got, summary, want, untied, spec,
                      np.array([[[1, 0], [0, 0]]]), np.ones((1, 2, 1)), {})
    other = ({"lc_slot_gap", "lc_slot_gap_util_order"} - {gap}).pop()
    assert out[gap] == pytest.approx(1e-3)
    assert out[other] == 0
    assert out["event_gap"] == 0 and out["conservation_gap"] == 0


def test_the_control_departs_from_the_reference(small):
    ref = _reference(small, "ogasched")
    ctl = _reference(small, "ogasched", jnp.bfloat16)
    assert (ctl["departed"] != ref["departed"]).any()


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The benchmark at a test's size, the new deployment shrunk too: a
    grid past DEVICE_TRACE_MIN_POINTS, so synthesis takes the device path
    as on the chip."""
    root = str(tmp_path_factory.mktemp("bench"))
    tinybench.make(root)
    for kind, name, fields in (
            ("configs", "tab2_faults", {"R": 12, "T": 32}),
            ("traffic", "lifecycle", {"grid_seeds": 256, "chunk": 8})):
        path = os.path.join(root, "chipbench", kind, f"{name}.json")
        with open(path) as f:
            data = json.load(f)
        data.update(fields)
        with open(path, "w") as f:
            json.dump(data, f)
    return harness.Bench(root)


def _run(bench, **kw):
    return harness.run_cell(bench, WORKLOAD, tinybench.SEED,
                            kw.pop("seconds", 0.3), kw.pop("trace", False),
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


def test_the_cell_is_correct_end_to_end(bench):
    r = _run(bench)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 8 and r["failed"] == 0
    assert set(r["metrics"]) == {"scenario_slots_per_s", "setup_s"}
    assert set(r["compared"]) == {
        "spec_gap", "works_gap", "faults_gap", "lc_slot_gap",
        "lc_slot_gap_util_order", "event_gap", "summary_gap",
        "feasibility_excess", "conservation_gap"}
    for name in ("spec_gap", "works_gap", "faults_gap", "event_gap",
                 "conservation_gap"):
        assert r["compared"][name]["value"] == 0, name
    assert 0 < r["check_info"]["lc_slots_compared"] <= 1


def test_a_traced_run_reports_the_cell_metrics(bench):
    r = _run(bench, trace=True)
    assert r["correct"], r["compared"]
    # on the CPU no op carries the fused kernel's name, so
    # oga_kernel_busy_share finds nothing to read
    assert set(r["metrics"]) == {
        "device_idle_share.offline", "chunk_wait_share",
        "lifecycle_oga_busy_share", "lifecycle_baselines_busy_share",
        "evictions_per_kslot"}
    assert r["metrics"]["evictions_per_kslot"]["value"] > 0


def test_the_control_is_not_correct(bench):
    r = _run(bench, control=True)
    assert not r["correct"], r["compared"]
    assert r["compared"]["event_gap"]["value"] > 0


def _evict_fault(mp, edit):
    evict = lifecycle._evict

    def broken(spec, state, c_t, t, policy, depth):
        return edit(evict, spec, state, c_t, t, policy, depth)

    mp.setattr(lifecycle, "_evict", broken)


def _backoff_short(mp):
    """Re-queued jobs are ready one slot before their backoff ends."""
    def edit(evict, spec, state, c_t, t, policy, depth):
        new, gone, wasted = evict(spec, state, c_t, t, policy, depth)
        early = jnp.where(new.q_ready != state.q_ready, new.q_ready - 1,
                          new.q_ready)
        return dataclasses.replace(new, q_ready=early), gone, wasted
    _evict_fault(mp, edit)


def _keeps_one_more(mp):
    """The evicted job with the least work left stays in service."""
    def edit(evict, spec, state, c_t, t, policy, depth):
        new, gone, wasted = evict(spec, state, c_t, t, policy, depth)
        rank = jnp.where(gone, state.remaining, jnp.inf)
        spare = gone & (jnp.arange(spec.L) == jnp.argmin(rank))
        back = spare & (new.q_len > state.q_len)
        keep = lambda old, cur: jnp.where(
            back.reshape((-1,) + (1,) * (cur.ndim - 1)), old, cur)
        new = dataclasses.replace(
            new,
            held=jnp.where(spare[:, None, None], state.held, new.held),
            remaining=jnp.where(spare, state.remaining, new.remaining),
            q_work=keep(state.q_work, new.q_work),
            q_arr=keep(state.q_arr, new.q_arr),
            q_ready=keep(state.q_ready, new.q_ready),
            q_retry=keep(state.q_retry, new.q_retry),
            q_len=keep(state.q_len, new.q_len),
            rdropped=new.rdropped - jnp.sum(spare & ~back))
        return new, gone & ~spare, wasted
    _evict_fault(mp, edit)


def _restarts_from_zero(mp):
    """Evicted jobs start again from their whole work."""
    def edit(evict, spec, state, c_t, t, policy, depth):
        return evict(spec, state, c_t, t,
                     dataclasses.replace(policy, preserve_work=False), depth)
    _evict_fault(mp, edit)


FAULTS = {"backoff_short": _backoff_short, "keeps_one_more": _keeps_one_more,
          "restarts_from_zero": _restarts_from_zero}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(bench, monkeypatch, fault):
    jax.clear_caches()
    FAULTS[fault](monkeypatch)
    try:
        r = _run(bench)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not r["correct"], r["compared"]


# readers -------------------------------------------------------------------

MS = 1_000_000  # ns


def _reduced(calls, programs):
    """A tracereduce.Reduced of ``calls`` (start ms, end ms) benchmark
    spans, each holding the executions ``programs`` (module, start ms, end
    ms), one op covering each execution, on one TPU plane."""
    events = [SimpleNamespace(start_ns=s * MS, end_ns=e * MS,
                              name=f"%fusion.{i} = f32[8] fusion(%p)")
              for i, (_, s, e) in enumerate(programs)]
    modules = [SimpleNamespace(start_ns=s * MS, end_ns=e * MS,
                               name=f"{m}({i})")
               for i, (m, s, e) in enumerate(programs)]
    plane = SimpleNamespace(lines=[
        SimpleNamespace(name="XLA Ops", events=events),
        SimpleNamespace(name="XLA Modules", events=modules)])
    ops = tracereduce._Ops()
    tracereduce._tpu_ops(plane, 0, ops)
    spans = [("chipbench.window", 0, 1000 * MS)] + [
        ("chipbench.call", s * MS, e * MS) for s, e in calls]
    return tracereduce.Reduced(ops, spans, 1)


LC = "jit__grid_lifecycle"


def _read(metric, trace, stats=None):
    ctx = {"trace": trace, "stats": stats or {}, "config": {"T": 500}}
    return harness.Bench().reader(metric)(ctx)


def test_lifecycle_shares_by_order_of_dispatch():
    # two calls, five executions each: OGASched 40 ms, each heuristic 10
    programs = []
    for base in (0, 200):
        programs.append((LC, base, base + 40))
        programs += [(LC, base + 50 + 12 * i, base + 60 + 12 * i)
                     for i in range(4)]
    programs.append(("jit_other", 500, 520))  # outside every call
    red = _reduced([(0, 100), (200, 300)], programs)
    busy = 2 * 80 + 20
    assert _read("lifecycle_oga_busy_share", red) == pytest.approx(
        100 * 80 / busy)
    assert _read("lifecycle_baselines_busy_share", red) == pytest.approx(
        100 * 80 / busy)
    assert _read("device_idle_share.offline", red) == pytest.approx(
        100 * (1 - busy / 1000))


def test_lifecycle_shares_are_none_when_a_call_holds_another_count():
    programs = [(LC, 10 * i, 10 * i + 5) for i in range(4)]
    red = _reduced([(0, 100)], programs)
    assert red.by_order("^" + LC + "$", drive.ALGORITHMS) is None
    assert _read("lifecycle_oga_busy_share", red) is None
    assert _read("lifecycle_baselines_busy_share", red) is None
    slot_mode = _reduced([(0, 100)], [("jit__grid_ogasched", 0, 50)])
    assert _read("lifecycle_oga_busy_share", slot_mode) is None
    assert _read("device_idle_share.offline", None) is None


def test_evictions_per_kslot_reads_the_program_counter():
    stats = {"evictions": 300.0, "configs": 4}
    assert _read("evictions_per_kslot", None, stats) == pytest.approx(150.0)
    assert _read("evictions_per_kslot", None, {"configs": 4}) is None
    assert _read("evictions_per_kslot", None, {"evictions": 1.0}) is None
