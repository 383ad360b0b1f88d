"""The harness: the trace reduction, the look for a chip, and cells,
deployments and metrics found by name."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import harness
import tinybench
import tracereduce

# the two programs of the recorded trace, by XLA module name
PROGRAMS = {"matmul": r"^jit_matmul_step$", "reduce": r"^jit_reduce_step$"}


@jax.jit
def matmul_step(x):
    return jnp.tanh(x @ x)


@jax.jit
def reduce_step(x):
    return jnp.sum(jnp.cos(x), axis=0)


def _record_trace(trace_dir, traced_calls=None) -> harness.Window:
    x = jnp.ones((256, 256))
    matmul_step(x).block_until_ready()
    reduce_step(x).block_until_ready()
    win = harness.Window(trace_dir, traced_calls=traced_calls)
    win.open()
    for _ in range(3):
        with win.span("call"):
            matmul_step(x).block_until_ready()
            reduce_step(x).block_until_ready()
        with win.span("wait"):
            time.sleep(0.01)
    win.close(time.perf_counter())
    return win


def test_trace_reduction_on_a_small_cpu_trace(tmp_path):
    win = _record_trace(str(tmp_path))
    red = tracereduce.load(str(tmp_path), 1, platform="cpu")
    assert {"jit_matmul_step", "jit_reduce_step"} <= red.modules()
    busy, window = red.busy_s(), red.window_s()
    assert 0 < busy < window
    assert window == pytest.approx(win.seconds, rel=0.2, abs=0.01)
    times = {k: red.program_s([p]) for k, p in PROGRAMS.items()}
    assert all(t > 0 for t in times.values())
    assert sum(times.values()) <= busy * (1 + 1e-9)
    assert red.program_s([r"^no_such_program$"]) == 0
    out = red.breakdown()
    assert 0 < len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert any(k.startswith("jit_matmul_step/") for k, _ in out["device_ops"])
    assert out["idle_gaps"][0][0] == "wait"


def test_traced_calls_end_the_traced_window(tmp_path):
    win = _record_trace(str(tmp_path), traced_calls=1)
    red = tracereduce.load(str(tmp_path), 1, platform="cpu")
    calls = [s for s in red.spans if s[0] == "chipbench.call"]
    assert len(calls) == 1 and win.trace_stop_s is not None
    assert 0 < red.busy_s() <= red.window_s() < win.seconds - 0.015


DEVICE_READERS = ("device_idle_share.offline", "device_idle_share.online",
                  "oga_busy_share", "baselines_busy_share", "oga_roofline",
                  "device_ms_per_decision.online")


def test_tpu_trace_without_device_planes_gives_no_device_metric(tmp_path):
    """Host events are device operations only in a CPU rehearsal: a TPU
    run whose trace has no TPU plane reads no device metric."""
    _record_trace(str(tmp_path))
    red = tracereduce.load(str(tmp_path), 1, platform="tpu")
    assert red.n_ops == 0 and red.busy_s() == 0 and red.idle_pct() is None
    assert red.window_s() > 0  # the benchmark's own spans are still read
    bench = harness.Bench()
    ctx = {"trace": red, "device": {"count": 1, "kind": "TPU v5 lite"},
           "stats": {"oga_decisions": 100, "oga_shape": (10, 128, 6),
                     "decisions": 100, "window_s": 1.0}}
    assert {name: bench.reader(name)(ctx) for name in DEVICE_READERS} == (
        dict.fromkeys(DEVICE_READERS))


def _run_py(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "tab2.sweep",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run_py(harness.ROOT, env)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU chip" in out.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run_py(str(tmp_path), dict(env, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


# a driver added as a file: the sweep, checked by a comparison of its own
NEW_DRIVER = '''
import drive

sweep = drive.load_driver("sweep")


def compare(config, rows, control, info):
    return {"spec_gap": sweep.compare_slot(config, rows, control, info)[
        "spec_gap"]}


def run(config, traffic, **kw):
    return sweep.run(config, traffic, compare=compare, **kw)
'''


def test_new_cell_config_and_metric_are_found_by_name(tmp_path):
    """A new deployment, traffic mix, driver, limits file and metric, added
    as files and entries with no existing file edited, are found by name."""
    bench = tinybench.make(str(tmp_path))
    before = _digests(tmp_path / "chipbench")
    cb = tmp_path / "chipbench"
    with open(cb / "configs" / "tab2.json") as f:
        dense = json.load(f)
    dense["name"] = "tab2_dense"
    dense["density"] = 0.8
    (cb / "configs" / "tab2_dense.json").write_text(json.dumps(dense))
    (cb / "drivers" / "sweep_specs.py").write_text(NEW_DRIVER)
    with open(cb / "traffic" / "sweep.json") as f:
        small = dict(json.load(f), driver="sweep_specs", chunk=4,
                     grid_seeds=256, prefetch=1, checkpoint=True)
    (cb / "traffic" / "sweep_small.json").write_text(json.dumps(small))
    (cb / "limits" / "tab2_dense.sweep_small.json").write_text(
        json.dumps({"spec_gap": 1e-5}))
    (cb / "metrics" / "calls_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['stats']['calls'])\n")
    (cb / "metrics" / "chunks_committed.py").write_text(
        "def read(ctx):\n"
        "    return float(ctx['stats']['checkpoint_commits'])\n")
    spec = dict(bench.spec)
    spec["configs"] = spec["configs"] + [{
        "name": "tab2_dense", "source": "test", "reduced": ["T"],
        "file": "chipbench/configs/tab2_dense.json", "why": "test"}]
    spec["workloads"] = spec["workloads"] + [{
        "name": "tab2_dense.sweep_small", "config": "tab2_dense",
        "traffic": "sweep_small", "chips": 1, "why": "test"}]
    spec["per_layer"] = spec["per_layer"] + [{
        "name": name, "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "sweep driver",
        "moves": "scenario_slots_per_s",
        "workloads": ["tab2_dense.sweep_small"]}
        for name in ("calls_in_window", "chunks_committed")]
    spec["end_to_end"] = [
        dict(m, workloads=m["workloads"] + ["tab2_dense.sweep_small"])
        if m["name"] == "scenario_slots_per_s" else m
        for m in spec["end_to_end"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digests(tmp_path / "chipbench")
    assert {k: after[k] for k in before} == before
    bench = harness.Bench(str(tmp_path))
    r = tinybench.run(bench, "tab2_dense.sweep_small", trace=True)
    assert r["correct"], r["compared"]
    assert set(r["compared"]) == {"spec_gap"}
    assert set(r["metrics"]) == {"calls_in_window", "chunks_committed"}
    calls = r["metrics"]["calls_in_window"]["value"]
    assert calls >= 1 and r["metrics"]["chunks_committed"]["value"] == calls
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    r = tinybench.run(bench, "tab2_dense.sweep_small")
    assert set(r["metrics"]) == {"scenario_slots_per_s", "setup_s"}


def test_sweep_driver_refuses_a_mode_it_has_no_reference_for():
    config = harness.Bench().config("tab2")
    traffic = dict(harness.Bench().traffic("sweep"), mode="lifecycle")
    with pytest.raises(ValueError, match="no reference for mode"):
        harness.Bench().driver("sweep")(
            config, traffic, seed=1, seconds=1.0,
            window=harness.Window(None), devices=jax.devices())
