"""Thm. 1 statistical validation (regret certificate, machine-readable).

A single (seed, utility, T) regret number cannot test "R_T <= H_G sqrt(T),
sublinear" — this bench runs the core.regret validation engine instead:
seeds x utility families x arrival regimes stream through the chunked
curve engine, each (utility, regime) cell gets

  * the seed-averaged log-log growth exponent of R_t with a bootstrap CI
    (`regret.bootstrap_exponent`) — sublinear means exponent < 1.0;
  * the literal Thm. 1 check mean R_T <= H_G sqrt(T).

`run` returns one record per cell; `benchmarks.run` serialises them to
``BENCH_regret.json`` (the CI ``regret-gate`` job fails on any cell with
exponent >= 1.0 or a violated bound). Unfittable cells — regret so small
or negative the log-log fit has no support — carry ``exponent: None`` and
a visible warning, not a silent NaN.
"""
from __future__ import annotations

import math

from benchmarks.common import emit
from repro import compat
from repro.core import regret
from repro.sched import trace


def run(quick: bool = True) -> list[dict]:
    T = 2048 if quick else 16384
    seeds = tuple(range(4 if quick else 8))
    base = trace.TraceConfig(T=T, L=6, R=16, K=4, contention=10.0)
    points, labels = regret.make_regret_grid(
        base, regimes=("stationary", "flash"), seeds=seeds,
    )
    # the whole grid streams through one chunked driver, so XLA backend
    # compiles are a run-level quantity: every cell record carries the same
    # count as provenance (a jump between PRs means the driver started
    # recompiling per chunk — the bug class test_sanitizers.py pins at 0
    # for warm streams)
    with compat.CompilationCounter() as cc:
        records = regret.regret_validation(
            points, labels,
            chunk_size=16 if quick else 8,
            oracle_iters=1500,
            n_boot=200,
        )
    for r in records:
        # provenance the JSON needs to be interpretable on its own
        r.update(
            T=T, eta="theoretical(eq.50)", decay=1.0,
            jit_backend_compiles=cc.count,
        )
        exp, lo, hi = r["exponent"], r["ci_lo"], r["ci_hi"]
        emit(
            f"thm1.regret.{r['utility']}.{r['regime']}",
            0.0,
            f"exp={exp:.3f};ci=[{lo:.3f},{hi:.3f}];R_T={r['r_T_mean']:.1f};"
            f"bound={r['bound']:.1f};bound_ok={r['bound_ok']};"
            f"sublinear={r['sublinear']}",
        )
        if not math.isfinite(exp):
            print(
                f"# WARNING: {r['utility']}/{r['regime']}: too few usable "
                "curve points for a growth-exponent fit (regret small or "
                "negative); cell counts as sublinear but carries no exponent"
            )
        # NaN is not strict JSON; None round-trips everywhere
        for k in ("exponent", "ci_lo", "ci_hi"):
            if not math.isfinite(r[k]):
                r[k] = None
    return records


if __name__ == "__main__":
    run()
