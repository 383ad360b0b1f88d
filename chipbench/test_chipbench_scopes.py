"""The reading of the program's own scopes and spans (scopes.py), and the
fused kernel's busy share: on traces built by hand in the TPU's layout, and
on a CPU trace, where neither has anything to read."""
import json
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import harness
import scopes
import tracereduce
from repro import obs

MS = 1_000_000  # ns
DEVICE_PLANE = "/device:TPU:0"
OUTER = "jit(run_batch)/vmap(jit(run))/while/body/closed_call"


def _xspace(ops, spans, plane=DEVICE_PLANE):
    """An XSpace: ``ops`` (start ms, end ms, scope path) on the XLA Ops line
    of one TPU plane, the path in the ``tf_op`` stat of the op's metadata
    as the chip keeps it, the first op's interned (``ref_value``); ``spans``
    (name, start ms, end ms, thread line) on a host plane."""
    space = scopes._xspace_class()()
    dev = space.planes.add(name=plane)
    dev.stat_metadata.add(key=1).value.name = "tf_op"
    dev.stat_metadata.add(key=2).value.name = "hlo_category"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for i, (s, e, path) in enumerate(ops, start=1):
        md = dev.event_metadata.add(key=i).value
        md.name = f"%fusion.{i} = f32[8] fusion(%p)"
        md.stats.add(metadata_id=2, str_value="loop fusion")
        if path is not None and i == 1:
            dev.stat_metadata.add(key=100).value.name = path
            md.stats.add(metadata_id=1, ref_value=100)
        elif path is not None:
            md.stats.add(metadata_id=1, str_value=path)
        line.events.add(metadata_id=i, offset_ps=s * MS * 1000,
                        duration_ps=(e - s) * MS * 1000)
    host = space.planes.add(name="/host:CPU")
    threads = {}
    for i, (name, s, e, thread) in enumerate(spans, start=1):
        host.event_metadata.add(key=i).value.name = name
        if thread not in threads:
            threads[thread] = host.lines.add(name="python", timestamp_ns=0)
        threads[thread].events.add(metadata_id=i, offset_ps=s * MS * 1000,
                                   duration_ps=(e - s) * MS * 1000)
    return space


# the window is [10, 100] ms on thread 0; thread 1 is the prefetch worker
SPANS = [("chipbench.window", 10, 100, 0), ("chipbench.call", 10, 100, 0),
         ("repro.run_all.drf", 20, 60, 0), ("repro.run_all.wait", 40, 52, 0),
         ("repro.sweep.synthesis", 60, 80, 1)]
OPS = [
    (0, 30, f"{OUTER}/repro.heuristic.drf/reduce"),  # crosses the start
    (25, 35, f"{OUTER}/repro.heuristic.drf/add"),  # overlaps the one above
    (35, 45, f"{OUTER}/vmap(repro.reward)/tanh"),
    (40, 42, f"{OUTER}/repro.reward/repro.inner/mul"),  # nested scopes
    (50, 55, "jit(run)/while/body/repro.oga.update/oga_step_fused"),
    (90, 120, f"{OUTER}/repro.heuristic.binpacking/max"),  # crosses the end
    (57, 58, None),  # an op with no scope
]


def test_scope_seconds_by_union_clipped_to_the_window():
    tr = scopes.from_xspace(_xspace(OPS, SPANS), 1)
    assert (tr.lo, tr.hi) == (10 * MS, 100 * MS)
    assert tr.scope_s("heuristic.drf") == pytest.approx(25e-3)
    assert tr.scope_s("reward") == pytest.approx(10e-3)
    assert tr.scope_s("inner") == pytest.approx(2e-3)
    assert tr.scope_s("oga.update") == pytest.approx(5e-3)
    assert tr.scope_s("heuristic.binpacking") == pytest.approx(10e-3)
    assert tr.scope_s("heuristic.spreading") is None
    busy = 35e-3 + 5e-3 + 1e-3 + 10e-3
    assert tr.busy_s() == pytest.approx(busy)
    assert tr.shares() == pytest.approx({
        "heuristic_busy_share.drf": 100 * 25e-3 / busy,
        "heuristic_busy_share.binpacking": 100 * 10e-3 / busy,
        "reward_busy_share": 100 * 10e-3 / busy,
        "oga_update_busy_share": 100 * 5e-3 / busy})


def test_scopes_in_unwraps_transforms():
    assert scopes.scopes_in(
        "jit(f)/vmap(vmap(repro.reward))/repro.heuristic.drf/x") == {
        "reward", "heuristic.drf"}
    assert scopes.scopes_in("jit(f)/while/body/tanh") == set()


def test_gaps_take_the_innermost_span_of_the_window_thread():
    tr = scopes.from_xspace(_xspace(OPS, SPANS), 1)
    gaps = dict((round(s * 1e3), label) for label, s in tr.idle_gaps())
    # 45-50 ms in run_all.wait, 55-57 ms in run_all.drf; 58-90 ms in the
    # benchmark's call alone on this thread, the worker's synthesis span
    # (60-80 ms), shorter, being on another
    assert gaps == {5: "repro.run_all.wait", 2: "repro.run_all.drf",
                    32: "call"}
    assert tr.span_s()["repro.run_all.wait"] == pytest.approx([1, 12e-3,
                                                               12e-3])
    assert tr.span_s()["repro.sweep.synthesis"] == pytest.approx(
        [1, 20e-3, 20e-3])
    (longest,) = tr.longest("call")
    assert longest["s"] == pytest.approx(90e-3)
    assert longest["busy_s"] == pytest.approx(tr.busy_s())
    assert longest["inside"] == pytest.approx({"repro.run_all.drf": 40e-3,
                                               "repro.run_all.wait": 12e-3})


def test_load_reads_the_trace_file(tmp_path):
    space = _xspace(OPS, SPANS)
    os.makedirs(tmp_path / "plugins" / "profile" / "run")
    with open(tmp_path / "plugins" / "profile" / "run" / "h.xplane.pb",
              "wb") as f:
        f.write(space.SerializeToString())
    tr = scopes.load(str(tmp_path), 1)
    assert tr.scope_s("reward") == pytest.approx(10e-3)
    assert json.loads(json.dumps(scopes.summary(tr)))["shares"] == (
        pytest.approx(tr.shares()))
    assert tr.idle_gaps() == scopes.from_xspace(space, 1).idle_gaps()


def test_no_reading_without_scopes_or_without_a_tpu_plane():
    bare = [(s, e, None) for s, e, _ in OPS]
    tr = scopes.from_xspace(_xspace(bare, SPANS), 1)
    assert tr.n_ops and tr.scope_s("reward") is None and tr.shares() == {}
    tr = scopes.from_xspace(_xspace(OPS, SPANS, plane="/device:GPU:0"), 1)
    assert not tr.n_ops and tr.shares() == {}


@jax.jit
def _step(x):
    with obs.scope("reward"):
        return jnp.tanh(x @ x)


def _cpu_trace(trace_dir):
    x = jnp.ones((128, 128))
    _step(x).block_until_ready()
    win = harness.Window(trace_dir)
    win.open()
    for _ in range(2):
        with win.span("call"), obs.span("run_all.drf"):
            _step(x).block_until_ready()
            with obs.span("run_all.wait"):
                time.sleep(0.01)
    win.close(time.perf_counter())


def test_cpu_trace_reads_spans_and_no_scope(tmp_path):
    """XLA:CPU's ops carry no scope, and are no device's ops: every share
    is None, while the program's spans are read on the window's thread."""
    _cpu_trace(str(tmp_path))
    tr = scopes.load(str(tmp_path), 1)
    assert not tr.n_ops and tr.shares() == {}
    assert tr.span_s()["repro.run_all.drf"][0] == 2
    _, s, e, _ = next(s for s in tr.spans if s[0] == "repro.run_all.wait")
    assert tr.label((s + e) // 2) == "repro.run_all.wait"


def test_program_spans_leave_what_tracereduce_reads(tmp_path):
    """tracereduce keeps only the benchmark's spans: the program's spans
    change no label of its breakdown."""
    _cpu_trace(str(tmp_path))
    red = tracereduce.load(str(tmp_path), 1, platform="cpu")
    assert {n for n, *_ in red.spans} == {"chipbench.window",
                                          "chipbench.call"}
    assert {g[0] for g in red.breakdown()["idle_gaps"]} <= {"call",
                                                            "outside"}


def _reduced(names, window=(10, 100)):
    """A tracereduce.Reduced of ops (start ms, end ms, HLO op name) on one
    TPU plane, in the layout the chip's trace has."""
    events = [SimpleNamespace(start_ns=s * MS, end_ns=e * MS,
                              name=f"%{n} = f32[8] custom-call(%p)")
              for s, e, n in names]
    plane = SimpleNamespace(lines=[
        SimpleNamespace(name="XLA Ops", events=events),
        SimpleNamespace(name="XLA Modules", events=[SimpleNamespace(
            start_ns=0, end_ns=200 * MS, name="jit_run(7)")])])
    ops = tracereduce._Ops()
    tracereduce._tpu_ops(plane, 0, ops)
    lo, hi = window
    return tracereduce.Reduced(
        ops, [("chipbench.window", lo * MS, hi * MS)], 1)


def _kernel_share(trace):
    return harness.Bench().reader("oga_kernel_busy_share")({"trace": trace})


def test_kernel_share_counts_the_fused_kernel_in_the_window():
    red = _reduced([(0, 30, "oga_step_fused.8"), (20, 40, "oga_step_fused.8"),
                    (50, 60, "fusion.3"), (90, 130, "oga_step_fused.9"),
                    (60, 70, "oga_step_fused_pad.1")])
    # kernel: [10, 40] and [90, 100] of busy [10, 40], [50, 70], [90, 100]
    assert _kernel_share(red) == pytest.approx(100 * 40 / 60)


def test_kernel_share_is_none_without_the_kernel(tmp_path):
    assert _kernel_share(_reduced([(20, 40, "fusion.3")])) is None
    _cpu_trace(str(tmp_path))
    assert _kernel_share(tracereduce.load(str(tmp_path), 1,
                                          platform="cpu")) is None
    assert _kernel_share(None) is None


def test_a_traced_cell_carries_its_scope_summary(tmp_path):
    """scopes.run_cell is a traced harness run with the summary beside its
    result; on the CPU every share is None and spans are still read."""
    import tinybench

    bench = tinybench.make(str(tmp_path))
    # oga_roofline knows no peaks of a CPU
    bench.spec["per_layer"] = [m for m in bench.spec["per_layer"]
                               if m["name"] != "oga_roofline"]
    result = scopes.run_cell(bench, "fig5.replay", tinybench.SEED, 0.3,
                             require_tpu=False)
    assert result["correct"] and "oga_busy_share" in result["metrics"]
    assert "oga_kernel_busy_share" not in result["metrics"]
    found = result["scopes"]
    assert found["shares"] == {} and found["reduce_s"] >= 0
    assert {"repro.run_all.ogasched", "repro.run_all.wait",
            "repro.run_all.synthesis", "chipbench.call"} <= set(found["spans"])
    assert tracereduce.load is scopes.tracereduce.load  # put back
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
