"""The fig5.replay cell end to end on the CPU, at a size a test can hold:
sound runs are correct, and the control and each fault the cell can have
are not."""
import jax
import jax.numpy as jnp
import pytest

import tinybench
from repro.kernels import ops
from repro.sched import sweep

WORKLOAD = "fig5.replay"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tinybench.make(str(tmp_path_factory.mktemp("bench")))


def test_replay_cell_is_correct(bench):
    r = tinybench.run(bench, WORKLOAD)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"scenario_slots_per_s", "setup_s"}
    assert set(r["compared"]) == {"oga_slot_gap", "avg_gap", "summary_gap"}


def test_replay_control_is_not_correct(bench):
    r = tinybench.run(bench, WORKLOAD, control=True)
    assert not r["correct"], r["compared"]


def _state_unchanged(mp):
    mp.setattr(ops, "oga_update_spec", lambda spec, y, x, eta, **kw: y)


def _rewards_fault(mp, edit):
    run_algorithm = sweep.run_algorithm

    def broken(spec, arrivals, name, **kw):
        return edit(lambda a: run_algorithm(spec, a, name, **kw), arrivals)

    mp.setattr(sweep, "run_algorithm", broken)


def _half_batch(mp):
    def edit(run, arrivals):
        half = run(arrivals[: arrivals.shape[0] // 2])
        return jnp.concatenate([half, half])[: arrivals.shape[0]]
    _rewards_fault(mp, edit)


def _answer_altered(mp):
    _rewards_fault(mp, lambda run, a: run(a).at[-1].multiply(1.5))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_replay_fault_is_not_correct(bench, monkeypatch, fault):
    jax.clear_caches()
    FAULTS[fault](monkeypatch)
    try:
        r = tinybench.run(bench, WORKLOAD)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not r["correct"], r["compared"]
