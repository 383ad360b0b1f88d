"""Reduce a profiler trace of the measured window to per-layer numbers.

``load(trace_dir, devices, platform)`` reads the ``.xplane.pb`` that
``jax.profiler`` wrote and keeps three things:

* device operations: each op that ran on a chip, with its start, end and
  the jitted program (XLA module) it belongs to. On a TPU they are the
  events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, and
  their program is the ``XLA Modules`` event that holds them, and nothing
  else: a TPU trace without such planes has no device operations. Only
  where the run's platform is ``cpu``, which rehearses the benchmark and
  is never measured, are they the host events that carry an
  ``hlo_module`` stat;
* the benchmark's own host spans, named ``chipbench.<what>``, and the
  ``chipbench.window`` span that bounds the traced window;
* nothing else: every number below is computed from these.

Busy time is the union of a chip's op intervals inside the window, idle
share is 1 - busy / window, and a program's device time is the union of
the intervals of its ops. Programs are chosen by regular expressions on
the module name (programs.py). Times are kept in integer nanoseconds and
reduced with numpy, since a trace can hold millions of ops.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import warnings

import numpy as np

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def _union(starts, ends, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
    """Merged [start, end) intervals clipped to [lo, hi], as two arrays."""
    s, e = np.clip(starts, lo, hi), np.clip(ends, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > reach[:-1]
    at = np.flatnonzero(first)
    return s[at], np.maximum.reduceat(e, at)


def _length(merged) -> int:
    s, e = merged
    return int(np.sum(e - s))


def _op_name(text: str) -> str:
    """``%while.13 = (s32[], ...) while(...)`` -> ``while.13``: a TPU op
    event is named by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``: drop a trailing program id."""
    return re.sub(r"\(\d+\)$", "", name)


class _Ops:
    """Columns of device ops: device, start, end (ns), op and module by
    code, and which execution of its program each op belongs to."""

    def __init__(self):
        self.cols = {k: [] for k in ("device", "start", "end", "name",
                                     "module", "execution")}
        self.names: dict[str, int] = {}
        self.modules: dict[str, int] = {}

    def code(self, table: dict, key: str) -> int:
        return table.setdefault(key, len(table))

    def arrays(self) -> dict:
        return {k: np.asarray(v, np.int64) for k, v in self.cols.items()}


class Reduced:
    def __init__(self, ops: _Ops, spans: list[tuple[str, int, int]],
                 devices: int):
        self.cols = ops.arrays()
        self.op_names = [_op_name(n) for n in ops.names]
        self.module_names = list(ops.modules)
        self.spans = spans
        self.devices = max(devices, 1)
        windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if windows:
            self.lo, self.hi = windows[0]
        elif self.n_ops:
            self.lo = int(self.cols["start"].min())
            self.hi = int(self.cols["end"].max())
        else:
            self.lo = self.hi = 0

    @property
    def n_ops(self) -> int:
        return len(self.cols["start"])

    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _modules_matching(self, patterns) -> frozenset:
        rx = [re.compile(p) for p in patterns]
        return frozenset(i for i, m in enumerate(self.module_names)
                         if any(r.search(m) for r in rx))

    @functools.lru_cache(maxsize=None)
    def _busy(self, modules: frozenset | None = None) -> list:
        """Merged busy intervals of each chip, of the ops of ``modules``
        (codes), or of every op."""
        c = self.cols
        pick = np.ones(self.n_ops, bool) if modules is None else np.isin(
            c["module"], np.fromiter(modules, np.int64, len(modules)))
        return [_union(c["start"][pick & (c["device"] == d)],
                       c["end"][pick & (c["device"] == d)], self.lo, self.hi)
                for d in range(self.devices)]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        return sum(map(_length, self._busy())) * 1e-9 / self.devices

    def idle_pct(self) -> float | None:
        """100 * (1 - busy / window); None without device operations."""
        if not self.n_ops or self.hi <= self.lo:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    def program_s(self, patterns) -> float:
        """Device seconds of the programs whose module name matches one of
        ``patterns``, averaged over the chips used."""
        modules = self._modules_matching(patterns)
        if not modules:
            return 0.0
        return sum(map(_length, self._busy(modules))) * 1e-9 / self.devices

    def modules(self) -> set[str]:
        return {self.module_names[i] for i in np.unique(self.cols["module"])}

    def executions(self, pattern: str) -> list[tuple[int, int, float]]:
        """(start ns, end ns, device seconds) of each execution of the
        programs matching ``pattern`` on the first chip, in time order."""
        c = self.cols
        modules = self._modules_matching([pattern])
        pick = (c["device"] == 0) & np.isin(
            c["module"], np.fromiter(modules, np.int64, len(modules)))
        out = []
        for ex in np.unique(c["execution"][pick]):
            at = pick & (c["execution"] == ex)
            s, e = c["start"][at], c["end"][at]
            out.append((int(s.min()), int(e.max()),
                        _length(_union(s, e, self.lo, self.hi)) * 1e-9))
        return sorted(out)

    def by_order(self, pattern: str, order, span: str = "call"):
        """Device seconds per name of ``order``, where each benchmark span
        ``span`` runs one execution of the programs matching ``pattern``
        per name, in that order; None when a span holds another count."""
        runs = self.executions(pattern)
        out = dict.fromkeys(order, 0.0)
        for lo, hi in ((s, e) for n, s, e in self.spans
                       if n == SPAN_PREFIX + span):
            inside = [r for r in runs if lo <= r[0] <= hi]
            if len(inside) != len(order):
                return None
            for name, (_, _, busy) in zip(order, inside):
                out[name] += busy
        return out

    def _label(self, t: float) -> str:
        """The innermost benchmark span (other than the window) at t."""
        best = None
        for name, s, e in self.spans:
            if name != WINDOW_SPAN and s <= t <= e:
                if best is None or e - s < best[1]:
                    best = (name[len(SPAN_PREFIX):], e - s)
        return best[0] if best else "outside"

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the longest idle gaps by
        what the benchmark's host thread was doing in them."""
        c = self.cols
        inside = (np.minimum(c["end"], self.hi)
                  - np.maximum(c["start"], self.lo)).clip(0)
        key = c["module"] * len(self.op_names) + c["name"]
        keys, where = np.unique(key, return_inverse=True)
        per_key = np.bincount(where, weights=inside) * 1e-9 / self.devices
        ops = [[f"{self.module_names[k // len(self.op_names)]}/"
                f"{self.op_names[k % len(self.op_names)]}", float(t)]
               for k, t in sorted(zip(keys, per_key), key=lambda kt: -kt[1])
               [:top]]
        gaps = []
        for s, e in self._busy():
            lo = np.concatenate([[self.lo], e])
            hi = np.concatenate([s, [self.hi]])
            for i in np.argsort(lo - hi)[:top]:
                if hi[i] > lo[i]:
                    gaps.append([self._label((lo[i] + hi[i]) // 2),
                                 float((hi[i] - lo[i]) * 1e-9)])
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": ops, "idle_gaps": gaps[:top]}


def _tpu_ops(plane, device: int, ops: _Ops) -> None:
    lines = {line.name: line for line in plane.lines}
    if "XLA Ops" not in lines:
        return
    modules = sorted((e.start_ns, e.end_ns, _module_name(e.name))
                     for e in (lines["XLA Modules"].events
                               if "XLA Modules" in lines else ()))
    mod_start = np.asarray([m[0] for m in modules], np.int64)
    mod_end = np.asarray([m[1] for m in modules], np.int64)
    mod_code = np.asarray([ops.code(ops.modules, m[2]) for m in modules]
                          + [ops.code(ops.modules, "")], np.int64)
    start, end, name = [], [], []
    for e in lines["XLA Ops"].events:
        start.append(e.start_ns)
        end.append(e.end_ns)
        name.append(ops.code(ops.names, e.name))
    start = np.asarray(start, np.int64)
    # the module event that holds an op: the first to end at or after its
    # start, where that one has begun by then
    j = np.searchsorted(mod_end, start, side="left")
    held = j < len(modules)
    held[held] = mod_start[j[held]] <= start[held]
    execution = np.where(held, j, -1)
    ops.cols["device"] += [device] * len(start)
    ops.cols["start"] += start.tolist()
    ops.cols["end"] += end
    ops.cols["name"] += name
    ops.cols["module"] += mod_code[np.where(held, j, len(modules))].tolist()
    ops.cols["execution"] += execution.tolist()


def _host_spans(plane) -> list[tuple[str, int, int]]:
    return [(e.name, e.start_ns, e.end_ns)
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)]


def _host_ops(plane, ops: _Ops) -> None:
    """XLA:CPU's op events, which carry their module as a stat."""
    for line in plane.lines:
        for e in line.events:
            if e.name.startswith(("end: ", SPAN_PREFIX)):
                continue
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                st = {k: v for k, v in e.stats}
            if "hlo_module" in st:
                ops.cols["device"].append(int(st.get("device_ordinal", 0)))
                ops.cols["start"].append(e.start_ns)
                ops.cols["end"].append(e.end_ns)
                ops.cols["name"].append(ops.code(ops.names, e.name))
                ops.cols["module"].append(
                    ops.code(ops.modules, str(st["hlo_module"])))
                ops.cols["execution"].append(int(st.get("run_id", -1)))


def load(trace_dir: str, win_devices: int, platform: str) -> Reduced:
    """The reduction of the one trace under ``trace_dir``, of a run on
    ``win_devices`` devices of ``platform``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {trace_dir}, "
                                f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    ops, spans = _Ops(), []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and platform != "cpu":
            _tpu_ops(plane, int(m.group(1)), ops)
        elif plane.name.startswith("/host:"):
            spans += _host_spans(plane)
            if platform == "cpu":
                _host_ops(plane, ops)
    return Reduced(ops, spans, win_devices)
