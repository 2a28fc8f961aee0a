#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: for each seed, one short
run of the cell judged against its plain reference, and the control
judged by the same comparison: the reference computed one precision
lower (float8 e4m3) put in the program's place, on the same sample.
The control has to come out not correct.  One process, so set-up
compiles once.

    python3 bench/control.py --workload <cell> --seconds 10 --seeds 1 2 3

Prints two JSON lines per seed, the program's (``"side": "program"``)
and the control's (``"side": "control"``), each with its ``correct``
and every compared number.  Needs the chip the cell asks for, like
``bench/run.py``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec, cell = bench_run.load_cell(args.workload)
    bench_run.prepare()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was measured", file=sys.stderr)
        return 3
    from bench.lib import harness
    clock = harness.CompileClock()
    for seed in args.seeds:
        t = time.monotonic()
        r = harness.run_cell(spec, cell, seed=seed, seconds=args.seconds,
                             trace=False, t_start=t, clock=clock,
                             control=True)
        for side, v in (("program", r), ("control", r["control"])):
            print(json.dumps({"seed": seed, "side": side,
                              "correct": v["correct"],
                              "attempted": r["attempted"],
                              "checks": v["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
