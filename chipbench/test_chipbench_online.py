"""The fig5.online cell end to end on the CPU, at a size a test can hold:
sound runs are correct, and the control and each fault the cell can have
are not."""
import jax
import numpy as np
import pytest

import tinybench
from repro.kernels import ops
from repro.sched import job_manager

WORKLOAD = "fig5.online"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tinybench.make(str(tmp_path_factory.mktemp("bench")))


def test_online_cell_is_correct(bench):
    r = tinybench.run(bench, WORKLOAD)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 10 and r["failed"] == 0
    assert set(r["metrics"]) == {"decision_ms_p50", "decision_ms_p95",
                                 "setup_s"}
    assert set(r["compared"]) == {"y_gap", "grant_mismatch",
                                  "feasibility_excess"}


def test_online_control_is_not_correct(bench):
    r = tinybench.run(bench, WORKLOAD, control=True)
    assert not r["correct"], r["compared"]


def _state_unchanged(mp):
    mp.setattr(ops, "oga_update_spec", lambda spec, y, x, eta, **kw: y)


def _step_fault(mp, edit):
    step = job_manager.JobManager.step
    mp.setattr(job_manager.JobManager, "step",
               lambda self, arrivals: edit(lambda x: step(self, x), arrivals))


def _half_batch(mp):
    def edit(step, arrivals):
        x = np.array(arrivals)
        x[len(x) // 2:] = 0
        return step(x)
    _step_fault(mp, edit)


def _answer_altered(mp):
    def edit(step, arrivals):
        grants = step(arrivals)
        for port in list(grants)[:1]:
            grants[port] = 2 * grants[port] + 1
        return grants
    _step_fault(mp, edit)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_online_fault_is_not_correct(bench, monkeypatch, fault):
    jax.clear_caches()
    FAULTS[fault](monkeypatch)
    try:
        r = tinybench.run(bench, WORKLOAD)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not r["correct"], r["compared"]
