"""Benchmark driver — one section per paper table/figure + kernels +
roofline. Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common).
The sweep section additionally writes machine-readable ``BENCH_sweep.json``
(configs/sec at several grid sizes, streamed vs resident peak-memory
estimates) and the kernels section ``BENCH_kernels.json`` (projection +
fused-step timings, incl. the bisect64-vs-fused step A/B) so the perf
trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--full]
"""
from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale settings")
    ap.add_argument("--only", type=str, default="")
    ap.add_argument(
        "--sweep-json", type=str, default="BENCH_sweep.json",
        help="where the sweep section writes its machine-readable records",
    )
    ap.add_argument(
        "--kernels-json", type=str, default="BENCH_kernels.json",
        help="where the kernels section writes its machine-readable records "
        "(projection + fused-step timings, incl. the backend step A/B)",
    )
    ap.add_argument(
        "--faults-json", type=str, default="BENCH_faults.json",
        help="where the fault-injection section writes its machine-readable "
        "records (goodput/wasted-work/recovery per algorithm x regime + "
        "the degradation summary CI gates on)",
    )
    ap.add_argument(
        "--regret-json", type=str, default="BENCH_regret.json",
        help="where the Thm. 1 section writes its machine-readable records "
        "(per utility x regime: growth exponent + bootstrap CI, R_T vs "
        "the H_G sqrt(T) bound)",
    )
    args, _ = ap.parse_known_args()
    quick = not args.full

    from repro import compat

    compat.use_repo_compile_cache()

    from benchmarks import (
        bench_contention,
        bench_faults,
        bench_generality,
        bench_hparams,
        bench_kernels,
        bench_large_scale,
        bench_lifecycle,
        bench_regret,
        bench_reward,
        bench_roofline,
        bench_scalability,
        bench_sweep,
        bench_utilities,
    )

    def sweep_section():
        records = bench_sweep.run(quick)
        with open(args.sweep_json, "w") as f:
            json.dump(records, f, indent=2)
        print(f"# wrote {len(records)} sweep records to {args.sweep_json}")

    def kernels_section():
        records = bench_kernels.run(quick)
        records += bench_scalability.run_backends(quick)
        with open(args.kernels_json, "w") as f:
            json.dump(records, f, indent=2)
        print(f"# wrote {len(records)} kernel records to {args.kernels_json}")
        # one invocation emits BOTH roofline views: the dry-run table and
        # the measured-kernel rows just benchmarked
        bench_roofline.run(kernel_records=records)

    def regret_section():
        records = bench_regret.run(quick)
        with open(args.regret_json, "w") as f:
            json.dump(records, f, indent=2)
        print(f"# wrote {len(records)} regret records to {args.regret_json}")

    def faults_section():
        records = bench_faults.run(quick)
        with open(args.faults_json, "w") as f:
            json.dump(records, f, indent=2)
        print(f"# wrote {len(records)} fault records to {args.faults_json}")

    sections = [
        ("fig2_reward", lambda: bench_reward.run(T=1000 if quick else 8000)),
        ("tab3_generality", lambda: bench_generality.run(quick)),
        ("fig3_scalability", lambda: bench_scalability.run(quick)),
        ("fig4_hparams", lambda: bench_hparams.run(quick)),
        ("fig5_large_scale", lambda: bench_large_scale.run(quick)),
        ("fig6_contention", lambda: bench_contention.run(quick)),
        ("fig7_utilities", lambda: bench_utilities.run(quick)),
        ("thm1_regret", regret_section),
        ("sweep_throughput", sweep_section),
        ("lifecycle_jct", lambda: bench_lifecycle.run(quick)),
        ("lifecycle_faults", faults_section),
        ("kernels", kernels_section),
    ]
    for name, fn in sections:
        if args.only and args.only not in name:
            continue
        print(f"# --- {name} ---")
        t0 = time.time()
        fn()
        print(f"# {name} done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
