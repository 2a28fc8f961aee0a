#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are looked up by name
in ``BENCHMARK.json`` at the root of the checkout.  Set-up (weights,
encoding, compiling or loading every program the cell uses) comes
first, then ``--seconds`` of measured traffic, then the correctness
check.  With ``--trace 1`` the traffic runs on for a traced stretch
after the window (the window itself runs without the profiler) and the
cell's per-layer metrics are reported instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), then ``checks``, each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.  JAX's compilation cache lives in
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str):
    """``(BENCHMARK.json, its workload named name)``; raises KeyError."""
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        if w["name"] == name:
            return spec, w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def prepare() -> None:
    """The compile cache inside the checkout, the program on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None, *, require_tpu: bool = True) -> int:
    args = parse(argv)
    try:
        spec, cell = load_cell(args.workload)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    prepare()
    import jax
    devices = jax.devices()
    print(f"[bench] device: {devices[0].platform} "
          f"{devices[0].device_kind!r} x{len(devices)}; compile cache "
          f"{CACHE_DIR}", file=sys.stderr, flush=True)
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform!r} "
              f"device(s); nothing was measured", file=sys.stderr)
        return 3
    from bench.lib import harness
    clock = harness.CompileClock()
    result = harness.run_cell(spec, cell, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_start=T_START, clock=clock)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
