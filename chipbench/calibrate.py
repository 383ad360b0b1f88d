"""Readings that the limits of ``correct`` and the online rates are set from.

    python3 chipbench/calibrate.py --workload <name> --seeds 12 --control 3
    python3 chipbench/calibrate.py --workload <name> --rates 40,80,120

The first form runs the cell's driver on ``--seeds`` seeds in one process,
with a short window, and prints each run's compared numbers. On the first
``--control`` seeds it also prints the control's: the reference computed
in bfloat16 put in the program's place. The limit of each number lies
between the largest program reading (the lower reading) and the smallest
control reading (the upper one).

The second form runs an online cell at each slot rate of ``--rates`` and
prints its latency and how late slots started, to find the highest rate
the program sustains without a growing backlog.

It needs the chips the cell asks for, as run.py does. The benchmark's own
runs never run it.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--also", default="",
                    help="comma-separated seeds read after the others")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    import jax
    bench = harness.Bench()
    wl = bench.workload(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print(f"calibrate: {args.workload} needs {wl['chips']} TPU chip(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      harness.cache_dir(harness.ROOT))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    config = bench.config(wl["config"])
    traffic = bench.traffic(wl["traffic"])
    drive = bench.driver(traffic["driver"])

    def once(seed, traffic):
        win = harness.Window(None)
        run = drive(config, traffic, seed=seed, seconds=args.seconds,
                    window=win, devices=devices[:wl["chips"]])
        run.release()
        run.end_to_end["setup_s"] = win.setup_s
        return run

    if args.rates:
        knee = None
        for rate in (float(r) for r in args.rates.split(",")):
            run = once(args.first_seed, dict(traffic, rate_hz=rate))
            # sustained: slots of the last tenth start on time, on average
            # within a fifth of the slot interval
            held = run.stats["late_ms_last_tenth"] < 200.0 / rate
            knee = rate if held else knee
            print(json.dumps({"rate_hz": rate, "sustained": held,
                              **run.end_to_end, **run.stats}), flush=True)
        print(json.dumps({"knee_hz": knee}), flush=True)
        return 0

    lower, upper = {}, {}
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    seeds += [int(s) for s in args.also.split(",") if s]
    for i, seed in enumerate(seeds):
        run = once(seed, traffic)
        t0 = time.perf_counter()
        got = run.check()
        line = {"seed": seed, "program": got, "info": dict(run.info),
                "check_s": time.perf_counter() - t0, **run.end_to_end}
        for k, v in got.items():
            lower[k] = max(lower.get(k, 0.0), v)
        if i < args.control:
            ctl = run.check(control=True)
            line["control"] = ctl
            for k, v in ctl.items():
                upper[k] = min(upper.get(k, float("inf")), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
