"""Run one benchmark cell once and print its result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the deployment, ``configs[].file``;
* the traffic mix, ``chipbench/traffic/<traffic>.json``, a data file whose
  ``driver`` key names the driver, and whose ``traced_calls``, where it is
  given, ends the traced window of a ``--trace 1`` run after that many
  calls (see Window); every other key is the driver's;
* the driver, ``chipbench/drivers/<driver>.py``: the program's entry point
  it drives and the reference comparison it is checked by (see drive.py);
* the limits of that comparison, ``chipbench/limits/<workload>.json``;
* each per-layer metric's reader, ``chipbench/metrics/<metric>.py``, a file
  with a ``read(ctx)`` that returns a number, or None where the cell has
  nothing for it to read.

So a cell, a deployment, a driver or a metric is added as new files and
entries, with no existing file edited.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# host events in a --trace 1 run: 1 keeps the benchmark's own spans and
# leaves out the runtime's per-dispatch events
HOST_TRACER_LEVEL = 1
SPAN_PREFIX = "chipbench."


class Bench:
    """``BENCHMARK.json`` and the files it names, under one checkout."""

    def __init__(self, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def path(self, *parts) -> str:
        return os.path.join(self.root, "chipbench", *parts)

    def _load_json(self, path: str) -> dict:
        with open(path) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return self._load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._load_json(self.path("traffic", f"{name}.json"))

    def limits(self, workload: str) -> dict:
        return self._load_json(self.path("limits", f"{workload}.json"))

    def metrics(self, workload: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.spec[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        return load_module(self.path("metrics", f"{metric}.py")).read

    def driver(self, name: str):
        return load_module(self.path("drivers", f"{name}.py")).run


def load_module(path: str):
    """The module in the file ``path``: a driver or a metric's reader."""
    rel = os.path.relpath(path, os.path.dirname(path) + "/..")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"\W", "_", rel[:-3]), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# jax.monitoring events summed over set-up, by the name the run prints
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_load_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
_compile_totals: dict = {}
_program_s: dict = {}  # compile or cache load, by jitted function


def _on_duration(event: str, duration: float, fun_name: str = "", **_) -> None:
    key = COMPILE_EVENTS.get(event)
    if key:
        _compile_totals[key] = _compile_totals.get(key, 0.0) + duration
        if key == "compile_or_load_s":
            _compile_totals["programs"] = _compile_totals.get("programs", 0) + 1
            _program_s[fun_name] = _program_s.get(fun_name, 0.0) + duration


def _listen_to_compiles() -> None:
    import jax
    if not getattr(_listen_to_compiles, "done", False):
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listen_to_compiles.done = True


class Window:
    """The measured window: its clock, the profiler when ``--trace 1``,
    named host spans that the trace reduction reads, and the set-up before
    it, split at the drivers' marks.

    With ``traced_calls``, the profiler stops once that many ``call`` spans
    have ended: the traced window is then the start of the measured one.
    A cell whose calls each run millions of device ops sets it, so that the
    device's trace buffer holds the whole traced window."""

    def __init__(self, trace_dir: str | None, t_start: float | None = None,
                 traced_calls: int | None = None):
        self.trace_dir = trace_dir
        self.traced_calls = traced_calls
        self.t0 = self.t1 = None
        self._ann = None
        self._calls = 0
        self.compiles = None
        self.trace_stop_s = None
        self._marks = [("start", t_start or time.perf_counter())]
        self._compiles0 = dict(_compile_totals)
        self._programs0 = dict(_program_s)
        self.setup_phases = {}

    def mark(self, name: str) -> None:
        """End the set-up phase ``name`` (the time since the last mark)."""
        self._marks.append((name, time.perf_counter()))

    def open(self) -> float:
        import jax
        self.mark("warm_call")
        self.setup_phases = {
            f"{name}_s": t - t_prev
            for (_, t_prev), (name, t) in zip(self._marks, self._marks[1:])}
        self.setup_phases.update(
            {k: v - self._compiles0.get(k, 0) for k, v in _compile_totals.items()})
        self.setup_phases["programs_s"] = {
            k: v - self._programs0.get(k, 0.0) for k, v in _program_s.items()
            if v > self._programs0.get(k, 0.0)}
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = HOST_TRACER_LEVEL
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("chipbench.window")
            self._ann.__enter__()
        from repro import compat
        self._counter = compat.CompilationCounter().__enter__()
        self.t0 = time.perf_counter()
        return self.t0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    @contextlib.contextmanager
    def span(self, name: str):
        if self._ann is None:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        if name == "call":
            self._calls += 1
            if self.traced_calls and self._calls >= self.traced_calls:
                self._stop_trace()

    def _stop_trace(self) -> None:
        if self._ann is None:
            return
        import jax
        t = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()
        self.trace_stop_s = time.perf_counter() - t

    def close(self, t_last: float) -> None:
        """End the window at the last completion, ``t_last``."""
        self.t1 = t_last
        self._counter.__exit__(None, None, None)
        self.compiles = self._counter.count
        self._stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def setup_s(self) -> float:
        return self.t0 - self._marks[0][1]


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def cache_dir(root: str) -> str:
    """Where JAX keeps compiled programs: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else a fixed directory inside the checkout."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             cache: str | None = None,
             control: bool = False) -> dict | None:
    """One run of one cell. Returns the result line as a dict, or None when
    the chips the cell asks for are not there. ``control`` compares the
    lower-precision control in the program's place, which must come out
    not correct."""
    import jax

    wl = bench.workload(workload)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < wl["chips"]):
        print(f"chipbench: {workload} needs {wl['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    devices = devices[:wl["chips"]]
    if cache:
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _listen_to_compiles()

    config = bench.config(wl["config"])
    traffic = bench.traffic(wl["traffic"])
    limits = bench.limits(workload)
    drive = bench.driver(traffic["driver"])

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        win = Window(trace_dir, t_start, traffic.get("traced_calls"))
        win.mark("jax")
        run = drive(config, traffic, seed=seed, seconds=seconds, window=win,
                    devices=devices)
        device = device_info(devices)
        run.release()
        compared = run.check(control=control)
        reduced = None
        if trace_dir:
            import tracereduce
            t_reduce = time.perf_counter()
            reduced = tracereduce.load(trace_dir, len(devices),
                                       platform=device["platform"])
            if not reduced.n_ops:
                raise RuntimeError(
                    f"the trace of {workload} holds no operation of a "
                    f"{device['platform']} device")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = {"trace": reduced, "stats": run.stats, "config": config,
           "traffic": traffic, "workload": wl, "device": device}
    end_to_end = dict(run.end_to_end, setup_s=win.setup_s)
    metrics = {}
    if trace:
        for m in bench.metrics(workload, "per_layer"):
            value = bench.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
    else:
        for m in bench.metrics(workload, "end_to_end"):
            metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                  "unit": m["unit"]}

    checks = {}
    for name, value in compared.items():
        if name not in limits:
            raise KeyError(f"{workload}: no limit for compared number {name!r}")
        checks[name] = {"value": value, "limit": limits[name]}
    correct = (
        run.failed == 0
        and all(_finite(m["value"]) for m in metrics.values())
        and all(_finite(c["value"]) and c["value"] <= c["limit"]
                for c in checks.values()))
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device,
              "compiles_in_window": win.compiles,
              "setup_phases": win.setup_phases}
    if run.info:
        result["check_info"] = run.info
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
        result["trace_cost_s"] = {"stop": win.trace_stop_s,
                                  "reduce": time.perf_counter() - t_reduce}
    result["compared"] = checks
    return result


def main(argv=None, *, t_start: float, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = Bench(root)
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=t_start,
                      cache=cache_dir(root))
    if result is None:
        return 2
    print(f"window: {result['attempted']} attempted, "
          f"{result['compiles_in_window']} compiles", file=sys.stderr)
    print(f"setup: {json.dumps(result['setup_phases'])}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
