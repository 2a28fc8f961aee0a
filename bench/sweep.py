#!/usr/bin/env python3
"""Find the knee of an open-loop LM cell: the highest offered rate at
which the waiting queue does not grow across the window.  One process,
one set-up; the cell's own mix is sent at each rate in turn.

    python3 bench/sweep.py --workload lm_qwen25_3b.chat --seconds 20 \\
        --rates 3 4 5 6 7 8

Prints one JSON line per rate: the queue at the window's opening and
close, and the cell's end-to-end metrics at that rate.  The chosen rate
is then written into the mix's file by hand, as a number.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec, cell = bench_run.load_cell(args.workload)
    bench_run.prepare()
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing was measured", file=sys.stderr)
        return 3
    from bench.lib import harness, lm_stats, traffic
    from bench.runners import lm_batcher
    from repro.core.batching import ContinuousBatcher
    from bench.lib.lm_packs import make_params

    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(bench_run.ROOT / conf["file"]) as f:
        c = json.load(f)
    mix0 = traffic.load(cell["traffic"])
    cfg = lm_batcher.model_config(c)
    params = make_params(cfg, args.seed, bits=c["codr"]["bits"],
                         backend=c["codr"]["backend"])
    srv = mix0["server"]
    batcher = ContinuousBatcher(params, cfg, n_slots=srv["n_slots"],
                                max_len=srv["max_len"],
                                kv_dtype=c["kv_dtype"],
                                kv_page_size=c["kv_page_size"],
                                record_logits=True)
    for h in [batcher.submit(np.zeros(n, np.int32), max_new_tokens=2)
              for n in traffic.prompt_lengths(mix0)]:
        h.result()
    clock = harness.CompileClock()
    for rate in args.rates:
        mix = copy.deepcopy(mix0)
        mix["rate_per_s"] = rate
        run = harness.Run(config=c, mix=mix, seed=args.seed,
                          seconds=args.seconds, trace=False,
                          t_start=time.monotonic(), clock=clock)
        queue, stop = [], threading.Event()

        def watch():
            while not stop.is_set():
                queue.append((time.monotonic(), batcher.pending))
                time.sleep(0.25)

        w = threading.Thread(target=watch, daemon=True)
        w.start()
        reqs = traffic.requests(mix, args.seed, args.seconds,
                                cfg.vocab_size)
        recs, _, threads = lm_batcher.serve(run, batcher, reqs)
        stop.set()
        w.join()
        batcher.stop_async(drain=False)
        for t in threads:
            t.join(timeout=60)
        ctx = harness.Ctx(cell=cell, config=c, mix=mix,
                          seconds=args.seconds,
                          device_kind=jax.devices()[0].device_kind,
                          setup_s=0.0, t0=run.t0, t1=run.t1, requests=recs,
                          calls=[], model=lm_batcher.model_numbers(c),
                          wait_end=run.wait_end)
        inside = [q for t, q in queue if run.t0 <= t <= run.t1]
        half = len(inside) // 2
        print(json.dumps({
            "rate_per_s": rate,
            "queue_at_open": inside[0] if inside else None,
            "queue_at_close": inside[-1] if inside else None,
            "queue_mean_first_half": float(np.mean(inside[:half]))
            if half else None,
            "queue_mean_second_half": float(np.mean(inside[half:]))
            if half else None,
            "ttft_p95_ms": lm_stats.percentile_ms(lm_stats.ttfts(ctx), 95),
            "ttft_p50_ms": lm_stats.percentile_ms(lm_stats.ttfts(ctx), 50),
            "itl_p50_ms": lm_stats.percentile_ms(lm_stats.gaps(ctx), 50),
            "itl_p95_ms": lm_stats.percentile_ms(lm_stats.gaps(ctx), 95),
            "tokens_per_s": lm_stats.tokens_in(ctx, run.t0, run.t1)
            / args.seconds,
            "compiles_in_window": run.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
