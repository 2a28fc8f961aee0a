#!/usr/bin/env python3
"""Readings of the program's own spans: one traced run of a cell with the
span recorder on from the end of set-up until the traffic stops, the
spans put on the device trace's clock.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds 51

Prints one JSON line: the cell's per-layer metrics and the span metrics
of ``bench/span_metrics.json``, the breakdown with ``idle_by_span``,
the spans recorded by name, the clock checks (device program runs
against the spans around them, and how far the marker program moved
the mapping), the split of each request's time to
first token into queue wait, prefill and the rest, and the window's and
the traced stretch's rates.  Needs the chip the cell asks for, like
``bench/run.py``.

Temporary: this stands in for ``bench/run.py --trace 1`` until the
harness records the spans itself.  ``SpanRun`` and ``SpanCtx`` become
``harness.Run`` and ``harness.Ctx``, ``run_cell`` folds into
``harness.run_cell``, ``bench/span_metrics.json`` into
``BENCHMARK.json``, and this file is deleted (PERF.md, Open questions).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
import jax  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench.lib import harness, spans, traffic, xtrace  # noqa: E402
from repro.runtime import tracing  # noqa: E402

SPAN_METRICS = ROOT / "bench" / "span_metrics.json"


def span_metrics() -> list[dict]:
    return json.loads(SPAN_METRICS.read_text())


def clock_marker(x):
    return x + 1


class SpanRun(harness.Run):
    """A traced run that records the program's spans from the end of
    set-up until the traffic stops, and reads the clock pair right after
    the profiler starts, then runs ``jit_clock_marker`` once between two
    stamps of the host's clock."""
    pair = None
    marker = None
    spans = ()
    _marker = staticmethod(jax.jit(clock_marker))

    def end_setup(self) -> None:
        jax.block_until_ready(self._marker(0))          # compile it now
        super().end_setup()
        tracing.enable()

    def tick(self, now: float | None = None) -> None:
        started = self.trace_t0 is not None
        super().tick(now)
        if not started and self.trace_t0 is not None:
            self.pair = spans.clock_pair()
            a = time.monotonic_ns()
            jax.block_until_ready(self._marker(0))
            self.marker = (a, time.monotonic_ns())

    def close_window(self) -> None:
        super().close_window()
        tracing.disable()
        self.spans = tracing.drain()


@dataclasses.dataclass
class SpanCtx(harness.Ctx):
    spans: list | None = None           # program clock (monotonic ns)


def run_cell(spec: dict, cell: dict, *, seed: int, seconds: float,
             t_start: float, clock, config: dict | None = None,
             mix: dict | None = None) -> dict:
    """One traced run of ``cell`` with the program's spans recorded, as
    ``harness.run_cell`` makes it; ``config`` and ``mix`` stand in for
    the cell's files, as there."""
    if config is None:
        conf = next(c for c in spec["configs"]
                    if c["name"] == cell["config"])
        config = json.loads((harness.ROOT / conf["file"]).read_text())
    mix = traffic.load(cell["traffic"]) if mix is None else mix
    runner = importlib.import_module(f"bench.runners.{config['runner']}")
    run = SpanRun(config=config, mix=mix, seed=seed, seconds=seconds,
                  trace=True, t_start=t_start, clock=clock)
    run.profile_options = getattr(runner, "PROFILE_OPTIONS",
                                  harness.PROFILE_OPTIONS)
    out = runner.run(run)
    try:
        tr = xtrace.load_dir(run.trace_dir, run.trace_t1 - run.trace_t0)
        off = spans.offset_ns(run.pair, spans.profile_start_ns(run.trace_dir))
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    shift = spans.marker_shift(tr.programs, "jit_clock_marker",
                               *run.marker, off)
    tr = spans.attach(tr, run.spans, off + shift, run.trace_t0,
                      run.trace_t1)
    ctx = SpanCtx(cell=cell, config=config, mix=mix, seconds=seconds,
                  device_kind=jax.devices()[0].device_kind,
                  setup_s=run.setup_s, t0=run.t0, t1=run.t1,
                  requests=out.requests, calls=out.calls, model=out.model,
                  trace=tr, trace_t0=run.trace_t0, trace_t1=run.trace_t1,
                  wait_end=out.wait_end, spans=run.spans)
    full = dict(spec, per_layer=spec["per_layer"] + span_metrics())
    metrics = {}
    for m in harness.metrics_for(full, cell["name"], "per_layer"):
        v = harness.read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return {"correct": bool(out.correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics,
            "device": {"kind": ctx.device_kind, "busy_s": tr.busy_s,
                       "window_s": tr.window_s},
            "breakdown": tr.breakdown(),
            "spans": dict(collections.Counter(s.name for s in run.spans)),
            "span_ms": spans.durations_ms(ctx),
            "clock": dict(spans.clock_checks(tr),
                          marker_shift_ms=shift / 1e6),
            "ttft_split": spans.ttft_split(ctx) if out.requests else None,
            "rates": {"window": harness._rate(out, run.t0, run.t1),
                      "traced": harness._rate(out, run.trace_t0,
                                              run.trace_t1)},
            "checks": {n: {"value": v, "limit": lim}
                       for n, v, lim in out.checks}}


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    spec, cell = bench_run.load_cell(args.workload)
    bench_run.prepare()
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("spans: no TPU; nothing was measured", file=sys.stderr)
        return 3
    result = run_cell(spec, cell, seed=args.seed, seconds=args.seconds,
                      t_start=T_START, clock=harness.CompileClock())
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
