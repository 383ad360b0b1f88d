"""The tab2.sweep cell end to end on the CPU, at a size a test can hold:
sound runs are correct, and the control and each fault the cell can have
are not."""
import jax
import pytest

import tinybench
from repro.kernels import ops
from repro.sched import sweep

WORKLOAD = "tab2.sweep"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tinybench.make(str(tmp_path_factory.mktemp("bench")))


def test_sweep_cell_is_correct(bench):
    r = tinybench.run(bench, WORKLOAD)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 8 and r["failed"] == 0
    assert set(r["metrics"]) == {"scenario_slots_per_s", "setup_s"}
    assert list(r)[-1] == "compared"
    assert set(r["compared"]) == {"spec_gap", "oga_slot_gap", "avg_gap",
                                  "summary_gap"}


def test_sweep_control_is_not_correct(bench):
    r = tinybench.run(bench, WORKLOAD, control=True)
    assert not r["correct"], r["compared"]


def _state_unchanged(mp):
    mp.setattr(ops, "oga_update_batch", lambda spec, y, x, eta, **kw: y)


def _grid_fault(mp, edit):
    run_grid = sweep.run_grid

    def broken(batch, *args, **kw):
        return edit(batch, run_grid(batch, *args, **kw))

    mp.setattr(sweep, "run_grid", broken)


def _half_batch(mp):
    def edit(batch, out):
        h = batch.size // 2
        return {n: v.at[h:].set(v[:batch.size - h]) for n, v in out.items()}
    _grid_fault(mp, edit)


def _answer_altered(mp):
    def edit(batch, out):
        return {**out, "ogasched": out["ogasched"].at[:, -1].multiply(1.5)}
    _grid_fault(mp, edit)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_sweep_fault_is_not_correct(bench, monkeypatch, fault):
    jax.clear_caches()
    FAULTS[fault](monkeypatch)
    try:
        r = tinybench.run(bench, WORKLOAD)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not r["correct"], r["compared"]


def test_rewards_after_a_near_tie_are_not_compared():
    """OGASched's rewards after a k* near-tie depend on which valid
    subgradient float32 took: they are left out of oga_slot_gap and of
    OGASched's average, and every slot before the tie is compared."""
    import numpy as np

    import drive
    import reference

    bs = np.array([[1.0, 1.0 - 0.1 * reference.TIE, 0.2],   # near-tie
                   [0.0, 0.0, 0.0]])                         # all zero
    assert bool(reference.near_tie(bs, np.array([1.0, 1.0])))
    assert not bool(reference.near_tie(bs, np.array([0.0, 1.0])))
    T, tie = 8, 5
    ref = {n: np.full((1, T), 2.0) for n in drive.ALGORITHMS}
    prog = {n: v.copy() for n, v in ref.items()}
    prog["ogasched"][0, tie + 1:] += 0.5  # the other side of the tie
    avg = {n: v.mean(axis=1) for n, v in prog.items()}
    gain = {n: reference.improvement_pct(avg["ogasched"], avg[n])
            for n in reference.HEURISTICS}
    got = drive.reward_gaps(prog, avg, gain, ref, np.array([tie + 1]))
    assert got == {"oga_slot_gap": 0.0, "avg_gap": 0.0, "summary_gap": 0.0}
    prog["ogasched"][0, tie] += 0.5  # a slot before the tie takes effect
    got = drive.reward_gaps(prog, avg, gain, ref, np.array([tie + 1]))
    assert got["oga_slot_gap"] == 0.25 and got["avg_gap"] > 0
    assert got["summary_gap"] > 0  # the summary no longer matches the rewards
