"""The program's scopes and spans (repro.obs): every name is in the table,
each device scope reaches the lowered program of the entry points that run
it, and each host span lands in a profiler trace."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import baselines, graph, ogasched
from repro.sched import job_manager, simulator, sweep, trace

SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
L, R, K, T, G = 4, 6, 3, 5, 2


def _names_in_source() -> set[str]:
    """Every scope and span name the program opens, an f-string's field
    written as ``<>``."""
    out = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            text = f.read()
        for name in re.findall(r'obs\.(?:scope|span)\(f?"([^"]+)"\)', text):
            out.add(re.sub(r"\{\w+\}", "<>", name))
    return out


def test_every_name_is_in_the_table_and_the_table_has_no_other():
    table = {re.sub(r"<\w+>", "<>", n) for n in obs.NAMES}
    assert _names_in_source() == table
    assert all(obs.NAMES.values())


def test_scope_and_span_carry_the_prefix():
    @jax.jit
    def f(x):
        with obs.scope("probe"):
            return jnp.tanh(x)

    assert "repro.probe/tanh" in f.lower(jnp.ones(3)).as_text(debug_info=True)
    with obs.span("probe"):  # no profiler running: a no-op
        pass


def _scopes(lowered) -> set[str]:
    text = lowered.as_text(debug_info=True)
    return {m for m in re.findall(r"repro\.[\w.]+", text)}


def _specs():
    spec = graph.make_random_spec(jax.random.PRNGKey(0), L=L, R=R, K=K)
    return spec, jax.tree.map(lambda l: jnp.stack([l] * G), spec)


@pytest.mark.parametrize("name", baselines.BASELINES)
def test_heuristic_programs_carry_their_scope_and_the_reward(name):
    _, specs = _specs()
    found = _scopes(baselines.run_batch.lower(specs, jnp.ones((G, T, L)),
                                              name))
    assert {f"repro.heuristic.{name}", "repro.reward"} <= found
    assert not {f"repro.heuristic.{n}" for n in baselines.BASELINES
                if n != name} & found


@pytest.mark.parametrize("entry", ["run", "run_batch"])
def test_ogasched_programs_carry_the_update_and_the_reward(entry):
    spec, specs = _specs()
    if entry == "run":
        lowered = ogasched.run.lower(spec, jnp.ones((T, L)), 1.0)
    else:
        lowered = ogasched.run_batch.lower(specs, jnp.ones((G, T, L)),
                                           jnp.ones(G), jnp.ones(G))
    assert {"repro.oga.update", "repro.reward"} <= _scopes(lowered)


def _host_spans(trace_dir) -> list[tuple[str, int]]:
    """(span name, thread line) of every repro span in the one trace."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [(e.name, i) for e in line.events
                        if e.name.startswith(obs.PREFIX)]
    return out


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(str(tmp_path))


def test_run_all_spans_each_algorithm_and_its_wait(tmp_path):
    cfg = trace.TraceConfig(T=T, L=L, R=R, K=K, seed=3)
    simulator.run_all(cfg)  # compile outside the trace
    spans = [n for n, _ in _traced(tmp_path, lambda: simulator.run_all(cfg))]
    algorithms = ("ogasched",) + baselines.BASELINES
    assert sorted(spans) == sorted(
        ["repro.run_all.synthesis"] + [f"repro.run_all.{a}"
                                       for a in algorithms]
        + ["repro.run_all.wait"] * len(algorithms))


def test_sweep_spans_wait_dispatch_summary_here_synthesis_on_the_worker(
        tmp_path):
    points = sweep.make_grid(trace.TraceConfig(T=T, L=L, R=R, K=K),
                             eta0s=(5.0,), seeds=(1, 2, 3, 4))

    def stream():
        for _, _, out in sweep.run_grid_stream(points, chunk_size=2,
                                               prefetch=2):
            sweep.summarize(out)

    stream()
    spans = _traced(tmp_path, stream)
    lines = {n: {i for m, i in spans if m == n} for n, _ in spans}
    assert [n for n, _ in spans].count("repro.sweep.synthesis") == 2
    assert [n for n, _ in spans].count("repro.sweep.dispatch") == 2
    assert [n for n, _ in spans].count("repro.sweep.wait") == 3
    assert [n for n, _ in spans].count("repro.sweep.summarize") == 2
    assert lines["repro.sweep.wait"] == lines["repro.sweep.dispatch"]
    assert not lines["repro.sweep.synthesis"] & lines["repro.sweep.wait"]


def test_online_step_spans_dispatch_copy_and_grants(tmp_path):
    jobs = [job_manager.JobTemplate(arch=f"job{l}", chips=4.0, hbm_gb=16.0)
            for l in range(L)]
    jm = job_manager.JobManager(
        job_manager.build_cluster(jobs, n_hosts=R, seed=0), jobs)
    x = jnp.asarray(np.ones(L, np.float32))
    jm.step(x)
    spans = [n for n, _ in _traced(tmp_path, lambda: jm.step(x))]
    assert spans == ["repro.online.dispatch", "repro.online.to_host",
                     "repro.online.grants"]


LIFECYCLE_SCOPES = {"repro.lifecycle.evict", "repro.lifecycle.queue",
                    "repro.lifecycle.allocate", "repro.lifecycle.service"}


def _lifecycle_lowered(name):
    from repro.sched import lifecycle

    _, specs = _specs()
    return sweep._run_grid_lifecycle.lower(
        specs, jnp.ones((G, T, L)), jnp.full((G, T, L), 3.0), jnp.ones(G),
        jnp.ones(G), jnp.asarray(1e-3), jnp.full((G, T, K), 0.7), name,
        "auto" if name == "ogasched" else "reference", 4,
        lifecycle.FaultPolicy())


@pytest.mark.parametrize("name", ("ogasched",) + baselines.BASELINES)
def test_lifecycle_programs_carry_their_steps_the_update_and_heuristic(name):
    found = _scopes(_lifecycle_lowered(name))
    assert LIFECYCLE_SCOPES <= found
    if name == "ogasched":
        assert "repro.oga.update" in found
        assert not {f"repro.heuristic.{n}" for n in baselines.BASELINES} \
            & found
    else:
        assert f"repro.heuristic.{name}" in found
        assert "repro.oga.update" not in found


def test_scopes_are_metadata_only(monkeypatch):
    """With every scope a no-op, the lifecycle and slot-mode programs lower
    to the same operations: a scope changes names, not arithmetic."""
    import contextlib

    _, specs = _specs()
    slot = lambda: baselines.run_batch.lower(specs, jnp.ones((G, T, L)),
                                             "drf")
    plain = lambda lowered: lowered.as_text(debug_info=False)
    with_scopes = [plain(_lifecycle_lowered("ogasched")),
                   plain(_lifecycle_lowered("spreading")), plain(slot())]
    assert not LIFECYCLE_SCOPES & _scopes(slot())
    jax.clear_caches()
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    try:
        without = [plain(_lifecycle_lowered("ogasched")),
                   plain(_lifecycle_lowered("spreading")), plain(slot())]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert with_scopes == without


def test_a_lifecycle_stream_spans_its_summary(tmp_path):
    points = sweep.make_grid(
        trace.TraceConfig(T=T, L=L, R=R, K=K,
                          faults=trace.FaultConfig(fail_rate=0.2)),
        eta0s=(5.0,), seeds=(1, 2))

    def stream():
        for _, batch, out in sweep.run_grid_stream(
                points, chunk_size=2, mode="lifecycle", prefetch=0):
            sweep.summarize_lifecycle(out, batch)

    stream()
    spans = [n for n, _ in _traced(tmp_path, stream)]
    assert spans.count("repro.sweep.summarize") == 1
    assert spans.count("repro.sweep.dispatch") == 1
