"""Roofline terms from compiled HLO (TPU v5e targets; CPU is the host).

    compute term    = HLO_FLOPs / (chips * 197e12 bf16 FLOP/s)
    memory term     = HLO_bytes / (chips * 819e9 B/s HBM)
    collective term = collective_bytes / (chips * 50e9 B/s ICI link)

cost_analysis() reports the partitioned (per-device) module; we scale by
device count for the global numerators so the formulas above hold.
Collective bytes are parsed from compiled HLO text: sum of operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import re
from typing import Optional

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s.
TPU_PEAKS = {
    "TPU v5 lite": {"peak_flops_s": 197e12, "peak_bytes_s": 819e9},
}
PEAK_FLOPS = TPU_PEAKS["TPU v5 lite"]["peak_flops_s"]   # bf16 / chip
HBM_BW = TPU_PEAKS["TPU v5 lite"]["peak_bytes_s"]       # B/s / chip
ICI_BW = 50e9             # B/s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(\(?[\w\[\],{}: ]*?\)?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_TYPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _nbytes(dtype: str, dims: str) -> int:
    n = _DTYPE_BYTES.get(dtype)
    if n is None:
        return 0
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-kind *operand*-byte totals + op counts from compiled HLO text.

    This XLA printer elides operand types, so we parse the output type(s) and
    convert: all-reduce/all-to-all/collective-permute operands equal outputs;
    all-gather operand = output / group_size; reduce-scatter operand =
    output * group_size. (-start async variants counted once; -done skipped.)
    """
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        out_types, kind = m.group(1), m.group(2)
        nbytes = sum(_nbytes(t, d) for t, d in _TYPE_RE.findall(out_types))
        if m.group(3):  # -start tuple repeats operand+result; halve
            nbytes //= 2
        g = _group_size(line)
        if kind == "all-gather":
            nbytes //= g
        elif kind == "reduce-scatter":
            nbytes *= g
        ent = out.setdefault(kind, {"bytes": 0, "count": 0})
        ent["bytes"] += int(nbytes)
        ent["count"] += 1
    return out


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode D = batch
    tokens per step."""
    n = cfg.n_active_params
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens  # forward only
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n * tokens


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_WHILE_RE = re.compile(r"while\(.*?\).*condition=%?([\w.\-]+).*body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> dict:
    """computation name -> list of body lines. Headers are lines ending in
    '{' without an '=' assignment (instruction lines always contain ' = ')."""
    comps: dict = {}
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and " = " not in s:
            m = _COMP_RE.match(s)
            if m:
                cur = m.group(1)
                comps[cur] = []
                if s.startswith("ENTRY"):
                    comps["__entry__"] = comps[cur]
                continue
        if cur is not None:
            if s == "}":
                cur = None
                continue
            comps[cur].append(line)
    return comps


def collective_bytes_exact(hlo_text: str) -> dict:
    """While-trip-count-aware collective accounting over the whole module.

    lax.scan lowers to while loops whose bodies XLA's cost/visit passes count
    once; here each computation's collectives are multiplied by the product
    of enclosing loop trip counts (parsed from the loop condition's compare
    constant). This is exact for the compiled artifact — no per-layer probe
    approximation (DESIGN.md §6)."""
    comps = _split_computations(hlo_text)

    def trip_count(cond_name: str) -> int:
        best = 1
        for line in comps.get(cond_name, ()):
            for c in _CONST_RE.findall(line):
                best = max(best, int(c))
        return best

    totals: dict = {}

    def visit(name: str, mult: int, seen: tuple):
        if name in seen:
            return
        for line in comps.get(name, ()):
            m = _COLL_RE.search(line)
            if m:
                nbytes = sum(
                    _nbytes(t, d) for t, d in _TYPE_RE.findall(m.group(1))
                )
                if m.group(3):
                    nbytes //= 2
                g = _group_size(line)
                kind = m.group(2)
                if kind == "all-gather":
                    nbytes //= g
                elif kind == "reduce-scatter":
                    nbytes *= g
                ent = totals.setdefault(kind, {"bytes": 0, "count": 0})
                ent["bytes"] += int(nbytes) * mult
                ent["count"] += mult
            w = _WHILE_RE.search(line)
            if w:
                cond, body = w.group(1), w.group(2)
                visit(body, mult * trip_count(cond), seen + (name,))

    visit("__entry__", 1, ())
    return totals


def hbm_traffic(memory: dict) -> float:
    """Per-device HBM traffic estimate from memory_analysis(): arguments and
    outputs move once, temps are written + read back once. The raw
    cost_analysis 'bytes accessed' ignores fusion and overestimates by >100x
    (EXPERIMENTS.md §Dry-run methodology), so the memory term uses this
    artifact-derived bound instead; the raw metric stays in cost_raw."""
    return (
        memory.get("argument_size_in_bytes", 0)
        + memory.get("output_size_in_bytes", 0)
        + 2.0 * memory.get("temp_size_in_bytes", 0)
    )


def dryrun_summary(record: dict) -> dict:
    """Derived fields of one dry-run artifact for table emission — the ONE
    home of this derivation, shared by benchmarks/bench_roofline (CSV rows)
    and analysis/report (markdown), so the two tables cannot drift.
    """
    tag = f"{record['arch']} / {record['shape']}"
    if record.get("variant"):
        tag += f" [{record['variant']}]"
    out = {"tag": tag, "status": record["status"]}
    if record["status"] != "ok":
        out["reason"] = record.get("reason", "")
        return out
    rl = record["roofline"]
    mf = record.get("model_flops", 0.0)
    out.update(
        dominant=rl["dominant"],
        t_compute_s=rl["t_compute_s"],
        t_memory_s=rl["t_memory_s"],
        t_collective_s=rl["t_collective_s"],
        t_dominant_s=max(
            rl["t_compute_s"], rl["t_memory_s"], rl["t_collective_s"]
        ),
        useful_flops=mf / max(rl["hlo_flops_global"], 1),
        temp_gb=record["memory"].get("temp_size_in_bytes", 0) / 1e9,
        model_flops=mf,
        kind=record.get("kind", "train"),
    )
    return out


# --------------------------------------------------------------------------
# Measured-kernel roofline: achieved bytes/s and flops/s of the *timed*
# scheduler kernels against a peak model. On TPU the peaks are the published
# per-chip constants of TPU_PEAKS, looked up by device kind; on a host backend
# they are CALIBRATED once per process — a large memcpy for bandwidth, a
# large f32 matmul for flops — so "fraction of peak" means fraction of what
# this machine demonstrably sustains, not of a TPU it is not.
# benchmarks/bench_kernels.py emits these records into BENCH_kernels.json.
# --------------------------------------------------------------------------

_kernel_peaks_cache: Optional[dict] = None


def _calibrate_host_peaks() -> dict:
    """Measured single-process peaks: copy bandwidth (read + write bytes
    over wall time, best of 3) and f32 matmul flops/s (best of 3)."""
    import time as _time

    import numpy as np

    n = 1 << 24  # 64 MiB f32 source
    src = np.ones(n, np.float32)
    bw = 0.0
    for _ in range(3):
        t0 = _time.perf_counter()
        dst = src.copy()
        dt = _time.perf_counter() - t0
        bw = max(bw, 2.0 * 4.0 * n / dt)
    del dst
    m = 1024
    a = np.ones((m, m), np.float32)
    fl = 0.0
    for _ in range(3):
        t0 = _time.perf_counter()
        a @ a
        dt = _time.perf_counter() - t0
        fl = max(fl, 2.0 * m**3 / dt)
    return {"peak_bytes_s": bw, "peak_flops_s": fl, "calibrated": True}


def kernel_peaks(
    platform: Optional[str] = None, device_kind: Optional[str] = None
) -> dict:
    """Peak model for the measured-kernel roofline, cached per process.

    TPU: the published peaks of ``device_kind`` (TPU_PEAKS); a TPU kind not
    in the table raises rather than borrowing another chip's peaks.
    Anything else: host-calibrated measured peaks (see module comment).
    """
    global _kernel_peaks_cache
    if platform == "tpu":
        if device_kind not in TPU_PEAKS:
            raise ValueError(
                f"no published peaks for TPU device kind {device_kind!r}; "
                f"known kinds: {sorted(TPU_PEAKS)}"
            )
        return {**TPU_PEAKS[device_kind], "calibrated": False}
    if _kernel_peaks_cache is None:
        _kernel_peaks_cache = _calibrate_host_peaks()
    return _kernel_peaks_cache


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def kernel_cost_model(
    kernel: str, n: int, l: int, method: str = "sortscan", iters: int = 20
) -> dict:
    """Analytic useful-work model {bytes, flops} for one kernel call on
    (n rows, l lanes), padded the way the kernels pad (lanes to 128).

    Bytes count each f32 operand read once and the output written once —
    the fused kernels are single-pass by construction, so this is the
    traffic a perfect memory system would move. Flops follow the method:
    bisect evaluates g per halving (~4 flops/lane/iter); sortscan runs a
    bitonic network over P = next_pow2(2 * lanes) lanes (~10 vector ops per
    lane per compare-exchange stage: partner selects, compares, the swap
    mask and the two swap selects) and two log2(P)-step rotate-and-add
    prefix sums (rotations move data and count no flops); "rows" models the
    off-TPU jnp packed-rows path (one real sort over the 2L breakpoints +
    prefix-sum sweep), so off-TPU measurements are compared against the
    work that implementation actually does, not the Pallas substitute.
    """
    lp = max(128, -(-l // 128) * 128)
    if kernel == "proj":
        # in: z, a, mask + per-row c; out: the projection
        nbytes = 4 * n * lp * 4 + n * 4
        grad_flops = 0.0
    elif kernel == "oga_step":
        # in: y, a, mask, x, kstar + the 128-lane scal block; out: y(t+1)
        nbytes = 6 * n * lp * 4 + n * 128 * 4
        grad_flops = 15.0 * n * lp  # eq. 30 gradient + ascent arithmetic
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    if method == "sortscan":
        p = _next_pow2(2 * lp)
        lg = p.bit_length() - 1
        stages = lg * (lg + 1) // 2
        proj_flops = n * (
            stages * 10 * p          # bitonic compare-exchange stages
            + 2 * 2 * lg * p         # two prefix sums: add + mask per step
            + 30.0 * lp              # closed-form segment finish
        )
    elif method == "rows":
        lg = (2 * lp - 1).bit_length()
        # sort compare-exchanges + prefix-sum sweep + segment finish
        proj_flops = n * lp * (4.0 * lg + 40.0)
    else:
        proj_flops = n * lp * (4.0 * iters + 20.0)
    return {"bytes": float(nbytes), "flops": float(grad_flops + proj_flops)}


def kernel_roofline(
    kernel: str,
    n: int,
    l: int,
    us: float,
    *,
    method: str = "sortscan",
    iters: int = 20,
    platform: Optional[str] = None,
    device_kind: Optional[str] = None,
    peaks: Optional[dict] = None,
) -> dict:
    """Measured achieved-vs-peak record for one timed kernel call.

    ``us`` is the measured wall time per call. Returns achieved bytes/s
    and flops/s from the analytic cost model, their fractions of the peak
    model, and which roof binds (the larger fraction — for these memory-
    bound kernels that is virtually always bytes).
    """
    cost = kernel_cost_model(kernel, n, l, method=method, iters=iters)
    pk = peaks or kernel_peaks(platform, device_kind)
    t = max(us, 1e-9) * 1e-6
    achieved_b = cost["bytes"] / t
    achieved_f = cost["flops"] / t
    frac_b = achieved_b / pk["peak_bytes_s"]
    frac_f = achieved_f / pk["peak_flops_s"]
    return {
        "kernel": kernel,
        "shape": f"N{n}xL{l}",
        "method": method,
        "us": float(us),
        "model_bytes": cost["bytes"],
        "model_flops": cost["flops"],
        "achieved_bytes_s": achieved_b,
        "achieved_flops_s": achieved_f,
        "peak_bytes_s": pk["peak_bytes_s"],
        "peak_flops_s": pk["peak_flops_s"],
        "frac_peak_bytes": frac_b,
        "frac_peak_flops": frac_f,
        "dominant": "memory" if frac_b >= frac_f else "compute",
        "peaks_calibrated": bool(pk.get("calibrated", False)),
    }


def roofline(record: dict, n_devices: int) -> dict:
    """record: one dry-run artifact (per-device flops/bytes + collectives)."""
    flops_g = record["cost"].get("flops", 0.0) * n_devices
    traffic = hbm_traffic(record.get("memory", {}))  # per device
    coll_per_dev = sum(v["bytes"] for v in record["collectives"].values())
    t_compute = flops_g / (n_devices * PEAK_FLOPS)
    t_memory = traffic / HBM_BW
    t_coll = coll_per_dev / ICI_BW  # per-device wire bytes over its links
    dom = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dom,
        "hlo_flops_global": flops_g,
        "hbm_traffic_per_device": traffic,
        "collective_bytes_per_device": coll_per_dev,
    }
