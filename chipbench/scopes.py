"""Device time by the program's own scopes, and idle gaps by its spans.

The program names its work (``repro.obs``): a scope ``repro.<name>`` reaches
the HLO metadata ``op_name`` of each device op traced inside it, and a span
``repro.<name>`` is a host event in the same profiler session as the
benchmark's ``chipbench.<name>`` spans. ``tracereduce`` keeps neither, so
this module reads them from the trace file itself:

    python3 chipbench/scopes.py --workload <name> --seed <n> --seconds <s>

runs one traced run of a cell as ``run.py --trace 1`` does and prints its
result line with one more key, ``scopes``: each ``SCOPE_METRICS`` share, the
longest idle gaps labelled by the innermost span (benchmark or program) on
the thread that holds ``chipbench.window``, the time in each span, and the
longest ``call`` and ``decision`` spans with what ran inside them.

A TPU op's scope path is the ``tf_op`` stat of its event's metadata, the
HLO op's ``op_name``; XLA:CPU's op events carry none, so on a CPU
rehearsal every share is None. The trace is parsed with the part of the
``XSpace`` schema (tsl/profiler/protobuf/xplane.proto) read here, since
``jax.profiler.ProfileData`` does not show an event's metadata stats.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

import numpy as np

import tracereduce

PREFIX = "repro."
WINDOW = tracereduce.WINDOW_SPAN
SPAN_PREFIXES = (tracereduce.SPAN_PREFIX, PREFIX)
# per-layer shares of device busy time, by the program scope each reads
SCOPE_METRICS = {
    "heuristic_busy_share.drf": "heuristic.drf",
    "heuristic_busy_share.fairness": "heuristic.fairness",
    "heuristic_busy_share.binpacking": "heuristic.binpacking",
    "heuristic_busy_share.spreading": "heuristic.spreading",
    "reward_busy_share": "reward",
    "oga_update_busy_share": "oga.update",
}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")

# message: fields as name:number:type, "*" marking a repeated field
_SCHEMA = {
    "XSpace": "planes:1:*XPlane",
    "XPlane": "name:2:string lines:3:*XLine event_metadata:4:*EventEntry "
              "stat_metadata:5:*StatEntry",
    "XLine": "name:2:string timestamp_ns:3:int64 events:4:*XEvent",
    "XEvent": "metadata_id:1:int64 offset_ps:2:int64 duration_ps:3:int64",
    "EventEntry": "key:1:int64 value:2:XEventMetadata",
    "XEventMetadata": "name:2:string stats:5:*XStat",
    "StatEntry": "key:1:int64 value:2:XStatMetadata",
    "XStatMetadata": "name:2:string",
    "XStat": "metadata_id:1:int64 str_value:5:string ref_value:7:uint64",
}


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                            package="chipbench_xplane")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for field in fields.split():
            name, number, kind = field.split(":")
            f = m.field.add(name=name, number=int(number),
                            label=F.LABEL_REPEATED if kind[0] == "*"
                            else F.LABEL_OPTIONAL)
            kind = kind.lstrip("*")
            if kind in _SCHEMA:
                f.type = F.TYPE_MESSAGE
                f.type_name = f".chipbench_xplane.{kind}"
            else:
                f.type = getattr(F, "TYPE_" + kind.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def union_s(cols: dict, pick, lo: int, hi: int, devices: int) -> float:
    """Seconds of the union of the picked ops' intervals on each chip,
    clipped to [lo, hi], averaged over the chips."""
    return sum(tracereduce._length(tracereduce._union(
        cols["start"][pick & (cols["device"] == d)],
        cols["end"][pick & (cols["device"] == d)], lo, hi))
        for d in range(devices)) * 1e-9 / devices


def scopes_in(path: str) -> set[str]:
    """The program scopes of a scope path, by name: ``jit(f)/while/body/
    vmap(repro.reward)/tanh`` -> {"reward"}."""
    out = set()
    for part in path.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part.startswith(PREFIX):
            out.add(part[len(PREFIX):])
    return out


class ScopeTrace:
    """A trace's device ops with their scope paths, and its benchmark and
    program spans with the thread each ran on."""

    def __init__(self, ops: dict, paths: list[str], spans: list, devices: int):
        self.cols = {k: np.asarray(v, np.int64) for k, v in ops.items()}
        self.paths = paths  # scope path by code; "" where the op has none
        self.spans = spans  # (name, start ns, end ns, thread)
        self.devices = max(devices, 1)
        window = [s for s in spans if s[0] == WINDOW]
        if window:
            _, self.lo, self.hi, self.thread = window[0]
        else:
            self.lo = int(self.cols["start"].min()) if self.n_ops else 0
            self.hi = int(self.cols["end"].max()) if self.n_ops else 0
            self.thread = None

    @property
    def n_ops(self) -> int:
        return len(self.cols["start"])

    def _seconds(self, pick) -> float:
        return union_s(self.cols, pick, self.lo, self.hi, self.devices)

    def busy_s(self) -> float:
        return self._seconds(np.ones(self.n_ops, bool))

    def scope_s(self, name: str) -> float | None:
        """Device seconds of the ops inside the program scope ``name``, a
        chip's union clipped to the window, averaged over the chips; None
        when no op carries that scope."""
        codes = [i for i, p in enumerate(self.paths) if name in scopes_in(p)]
        if not codes:
            return None
        return self._seconds(np.isin(self.cols["path"], codes))

    def shares(self) -> dict:
        busy = self.busy_s()
        out = {}
        for metric, name in SCOPE_METRICS.items():
            t = self.scope_s(name)
            if t is not None and busy > 0:
                out[metric] = 100.0 * t / busy
        return out

    def _window_spans(self) -> list:
        return [s for s in self.spans
                if s[3] == self.thread and s[0] != WINDOW]

    def label(self, t: int) -> str:
        """The innermost span on the window's thread at ``t``: a benchmark
        span by its name after ``chipbench.``, a program span in full."""
        best = None
        for name, s, e, _ in self._window_spans():
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        if best is None:
            return "outside"
        name = best[0]
        return name[len(tracereduce.SPAN_PREFIX):] if name.startswith(
            tracereduce.SPAN_PREFIX) else name

    def idle_gaps(self, top: int = 10) -> list:
        """The ``top`` longest idle gaps of any chip, [label, seconds]."""
        c, gaps = self.cols, []
        for d in range(self.devices):
            at = c["device"] == d
            s, e = tracereduce._union(c["start"][at], c["end"][at],
                                      self.lo, self.hi)
            lo = np.concatenate([[self.lo], e])
            hi = np.concatenate([s, [self.hi]])
            for i in np.argsort(lo - hi)[:top]:
                if hi[i] > lo[i]:
                    gaps.append([self.label((lo[i] + hi[i]) // 2),
                                 float((hi[i] - lo[i]) * 1e-9)])
        gaps.sort(key=lambda g: -g[1])
        return gaps[:top]

    def span_s(self) -> dict:
        """Seconds inside each span name, on any thread, clipped to the
        window: [count, total, longest]."""
        out = {}
        for name, s, e, _ in self.spans:
            if name == WINDOW:
                continue
            d = (min(e, self.hi) - max(s, self.lo)) * 1e-9
            if d > 0:
                n, total, most = out.get(name, (0, 0.0, 0.0))
                out[name] = (n + 1, total + d, max(most, d))
        return {k: list(v) for k, v in sorted(out.items())}

    def longest(self, span: str, top: int = 5) -> list:
        """The ``top`` longest ``chipbench.<span>`` spans in the window, each
        with the device's busy seconds in it and the program spans that ran
        inside it on its thread."""
        outer = sorted((s for s in self._window_spans()
                        if s[0] == tracereduce.SPAN_PREFIX + span
                        and s[1] >= self.lo),
                       key=lambda s: s[1] - s[2])[:top]
        out = []
        for _, s, e, _ in outer:
            inner = {}
            for name, s2, e2, _ in self._window_spans():
                if name.startswith(PREFIX) and s <= s2 and e2 <= e:
                    inner[name] = inner.get(name, 0.0) + (e2 - s2) * 1e-9
            out.append({"s": (e - s) * 1e-9, "at_s": (s - self.lo) * 1e-9,
                        "busy_s": union_s(self.cols, np.ones(self.n_ops, bool),
                                          s, e, self.devices),
                        "inside": inner})
        return out


def from_xspace(space, devices: int) -> ScopeTrace:
    """The ScopeTrace of a parsed ``XSpace`` of a run on ``devices`` TPU
    chips (or any object with the same fields)."""
    ops = {k: [] for k in ("device", "start", "end", "path")}
    paths: dict[str, int] = {"": 0}
    spans = []
    for p, plane in enumerate(space.planes):
        m = tracereduce._DEVICE_PLANE.match(plane.name)
        if m:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            code = {}  # event metadata id -> its scope path's code
            for entry in plane.event_metadata:
                path = ""
                for st in entry.value.stats:
                    if stat_names.get(st.metadata_id) == "tf_op":
                        path = (stat_names.get(st.ref_value, "")
                                if st.HasField("ref_value") else st.str_value)
                code[entry.key] = paths.setdefault(path, len(paths))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                base = line.timestamp_ns
                for ev in line.events:
                    ops["device"].append(int(m.group(1)))
                    ops["start"].append(base + ev.offset_ps // 1000)
                    ops["end"].append(
                        base + (ev.offset_ps + ev.duration_ps) // 1000)
                    ops["path"].append(code.get(ev.metadata_id, 0))
        elif plane.name.startswith("/host:"):
            names = {e.key: e.value.name for e in plane.event_metadata}
            for li, line in enumerate(plane.lines):
                base = line.timestamp_ns
                for ev in line.events:
                    name = names.get(ev.metadata_id, "")
                    if name.startswith(SPAN_PREFIXES):
                        s = base + ev.offset_ps // 1000
                        spans.append((name, s, s + ev.duration_ps // 1000,
                                      (p, li)))
    return ScopeTrace(ops, list(paths), spans, devices)


def load(trace_dir: str, devices: int) -> ScopeTrace:
    """The ScopeTrace of the one trace under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one trace under {trace_dir}, "
                                f"found {len(found)}")
    space = _xspace_class()()
    with open(found[0], "rb") as f:
        space.ParseFromString(f.read())
    return from_xspace(space, devices)


def summary(trace: ScopeTrace) -> dict:
    return {"busy_s": trace.busy_s(), "shares": trace.shares(),
            "idle_gaps": trace.idle_gaps(), "spans": trace.span_s(),
            "longest": {k: trace.longest(k) for k in ("call", "decision")}}


def run_cell(bench, workload: str, seed: int, seconds: float,
             **kw) -> dict | None:
    """``harness.run_cell`` traced, with the ``scopes`` summary of the same
    trace added to its result (its reduction time apart, ``reduce_s``).

    Scopes are metadata, which the persistent compilation cache leaves out
    of its key by default: an executable cached from a program without
    them would run here and carry none. So the run keys its cache entries
    by the metadata too."""
    import harness
    import jax

    found = {}
    load_reduced = tracereduce.load
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    def load_both(trace_dir, devices, platform):
        reduced = load_reduced(trace_dir, devices, platform)
        t = time.perf_counter()
        found.update(summary(load(trace_dir, devices)))
        found["reduce_s"] = time.perf_counter() - t
        return reduced

    # harness.run_cell reduces the trace and deletes it before it returns:
    # its call of tracereduce.load is where this reads the same file
    tracereduce.load = load_both
    try:
        result = harness.run_cell(bench, workload, seed, seconds, True,
                                  t_start=kw.pop("t_start",
                                                 time.perf_counter()), **kw)
    finally:
        tracereduce.load = load_reduced
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)
    if result is not None:
        result["scopes"] = found
    return result


def main(argv=None, *, t_start: float) -> int:
    import harness

    ap = argparse.ArgumentParser(
        description="One traced run of a cell, read by the program's scopes "
                    "and spans.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    result = run_cell(harness.Bench(), args.workload, args.seed, args.seconds,
                      t_start=t_start, cache=harness.cache_dir(harness.ROOT))
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    T_START = time.perf_counter()
    HERE = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))
    sys.exit(main(t_start=T_START))
