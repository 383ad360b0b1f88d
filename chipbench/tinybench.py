"""A copy of the benchmark at sizes a CPU test can hold, for the tests here.

``make(root)`` copies ``BENCHMARK.json`` and ``chipbench/`` under ``root``
and shrinks every deployment and traffic mix: fewer instances, ports and
slots, smaller chunks. Names, drivers, limits and metric readers are the
real ones, so a cell runs end to end through the same harness.
"""
from __future__ import annotations

import json
import os
import shutil

import harness

# deployment -> trace fields overridden; traffic -> parameters overridden
TRACE = {"tab2": {"R": 12, "T": 16}, "fig5": {"L": 20, "R": 24, "T": 16}}
TRAFFIC = {"sweep": {"grid_seeds": 256, "chunk": 8, "check_configs": 4},
           "online": {"rate_hz": 40.0}}
SEED = 2**31 + 17
# the cells the tests drive, one per driver
CELLS = {"tab2.sweep": ("tab2", "sweep"), "fig5.replay": ("fig5", "replay"),
         "fig5.online": ("fig5", "online")}


def _edit_json(path: str, edit) -> None:
    with open(path) as f:
        data = json.load(f)
    edit(data)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def make(root: str) -> harness.Bench:
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    for name, fields in TRACE.items():
        _edit_json(os.path.join(root, "chipbench", "configs", f"{name}.json"),
                   lambda c: c.update(fields))
    for name, fields in TRAFFIC.items():
        _edit_json(os.path.join(root, "chipbench", "traffic", f"{name}.json"),
                   lambda t: t.update(fields))

    def add_cells(spec):
        configs = {c["name"] for c in spec["configs"]}
        for name, (config, traffic) in CELLS.items():
            if config not in configs:
                configs.add(config)
                spec["configs"].append({
                    "name": config, "source": "test", "reduced": [],
                    "file": f"chipbench/configs/{config}.json", "why": "test"})
            if all(w["name"] != name for w in spec["workloads"]):
                spec["workloads"].append({
                    "name": name, "config": config, "traffic": traffic,
                    "chips": 1, "why": "test"})
        ends = {"sweep": ["scenario_slots_per_s"],
                "replay": ["scenario_slots_per_s"],
                "online": ["decision_ms_p50", "decision_ms_p95"]}
        for name, (_, traffic) in CELLS.items():
            for metric in ends[traffic]:
                m = next((m for m in spec["end_to_end"]
                          if m["name"] == metric), None)
                if m is None:
                    m = {"name": metric, "unit": "1", "better": "lower",
                         "bound": 0.25, "source": "host_clock",
                         "workloads": []}
                    spec["end_to_end"].append(m)
                if name not in m["workloads"]:
                    m["workloads"].append(name)

    _edit_json(os.path.join(root, "BENCHMARK.json"), add_cells)
    return harness.Bench(root)


def run(bench: harness.Bench, workload: str, *, trace: bool = False,
        control: bool = False, seconds: float = 0.3) -> dict:
    import time
    return harness.run_cell(bench, workload, SEED, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            control=control)
