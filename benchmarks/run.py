"""Benchmark orchestrator: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--list] \
      [--only table1_counters,fig5_proxyapps] [--no-measure]

Order mirrors the paper: counter calibration (Table 1), instruction-level
microbenchmarks (Figs 2-4), compiler-vs-kernel proxy apps (Figs 5-6), the
LMUL/block sweep (Figs 7-8), Qsim (Fig 9), then the roofline table from
the dry-run artifacts.  Every module writes its artifact through
``benchmarks.common.save_result`` in the canonical ``repro.perf.report``
schema (validate with ``python -m repro.perf --validate
benchmarks/results``).
"""
from __future__ import annotations

import argparse
import traceback

from repro.core.compile_cache import use_compile_cache
from repro.perf.measure import now

from benchmarks import (
    common,
    fig2_strided,
    fig3_tail,
    fig4_arith,
    fig5_proxyapps,
    fig6_breakdown,
    fig7_lmul,
    fig8_pressure,
    fig9_qsim,
    roofline_table,
    serve_bench,
    table1_counters,
)

BENCHMARKS = [
    ("table1_counters", table1_counters),
    ("fig2_strided", fig2_strided),
    ("fig3_tail", fig3_tail),
    ("fig4_arith", fig4_arith),
    ("fig5_proxyapps", fig5_proxyapps),
    ("fig6_breakdown", fig6_breakdown),
    ("fig7_lmul", fig7_lmul),
    ("fig8_pressure", fig8_pressure),
    ("fig9_qsim", fig9_qsim),
    ("roofline", roofline_table),
    ("serve_bench", serve_bench),
]


def main() -> None:
    use_compile_cache()
    names = [n for n, _ in BENCHMARKS]
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="NAME[,NAME...]",
                    help="comma-separated exact benchmark names "
                         "(see --list)")
    ap.add_argument("--list", action="store_true",
                    help="print available benchmark names and exit")
    ap.add_argument("--no-measure", action="store_true")
    args = ap.parse_args()

    if args.list:
        for n in names:
            print(n)
        return

    # unknown or empty --only selections error out listing the valid
    # names instead of silently running nothing (benchmarks.common)
    selected = common.select_benchmarks(args.only, names)

    results = []                               # (name, wall_s, ok)
    for name, mod in BENCHMARKS:
        if name not in selected:
            continue
        print(f"\n{'=' * 72}\nrunning {name}\n{'=' * 72}")
        t0 = now()
        try:
            mod.run(measure=not args.no_measure)
            results.append((name, now() - t0, True))
            print(f"[{name}] done in {results[-1][1]:.1f}s")
        except Exception as e:  # noqa: BLE001
            results.append((name, now() - t0, False))
            print(f"[{name}] FAILED: {e}")
            traceback.print_exc()
    print("\nsummary: " + " | ".join(
        f"{n} {'OK' if ok else 'FAIL'} {w:.1f}s" for n, w, ok in results))
    failures = [n for n, _, ok in results if not ok]
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("all benchmarks complete; JSON in benchmarks/results/")


if __name__ == "__main__":
    main()
