"""Benchmark harness — one module per paper table/figure.  Prints
``name,us_per_call,derived`` CSV lines.

  python -m benchmarks.run [--only fig6|compression|fig7|fig8|engine|
                                   decode]
                           [--small]

``compression`` is ``fig6`` plus the tuning-lane Pareto section (the
quality-vs-bits/weight curve and tuned-vs-global comparison written to
``BENCH_tune.json``).  ``--small`` runs the size-aware suites (engine —
the spec→compile→serve API path — decode, and compression) in their CI
smoke configuration; the CI workflow uses it so every PR appends a
comparable, SHA-stamped point to the ``BENCH_*.json`` perf
trajectories.
"""
from __future__ import annotations

import argparse
import sys

from benchmarks import compression, decode, energy, engine, sram_access
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "fig6": compression.main,
    "compression": compression.main,   # fig6 + tuning-lane Pareto curve
    "fig7": sram_access.main,
    "fig8": energy.main,
    "engine": engine.main,
    "decode": decode.main,
}
SMALL_AWARE = {"engine", "decode", "fig6", "compression"}  # small= kwarg


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=list(SUITES), default=None)
    ap.add_argument("--small", action="store_true",
                    help="CI smoke sizes for the suites that support it "
                         f"({', '.join(sorted(SMALL_AWARE))})")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.only:
        suites = {args.only: SUITES[args.only]}
    else:                       # run each suite once despite name aliases
        seen: set = set()
        suites = {n: f for n, f in SUITES.items()
                  if not (f in seen or seen.add(f))}
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        try:
            if args.small and name in SMALL_AWARE:
                fn(small=True)
            else:
                fn()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0.00,ERROR:{type(e).__name__}:{e}",
                  file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
