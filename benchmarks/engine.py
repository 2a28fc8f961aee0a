"""End-to-end CoDR engine benchmark: encode-once / run-many throughput
plus per-layer SRAM-access estimates from the dataflow model.

  PYTHONPATH=src python benchmarks/engine.py [--small] [--batch B]

Exercises the spec → compile → serve API (``repro.api``): a declarative
``ModelSpec`` on paper-CNN geometry is compiled once under an explicit
``EncodeConfig``, then driven through the offline bitstream decode, the
one-time compile, the steady-state (post-compile) forward — the
serving-relevant figure — and the batched request path, in all four
serving modes: the fused ``tiled`` backend, the ``sharded``
tile-parallel executor (over however many local devices the host
exposes — force more with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``), the
synchronous bucketed batch server, and the async futures path
(``submit_async`` + background flush loop) — plus the **transformer
serving mode**: an ``repro.models`` LM prefill/decode with every
projection executing from the packed bitstream through the decode-fused
``codr_matmul`` backend (``repro.launch.serve.run_serve``), with weight
HBM bytes measured on the stored pack, and the **continuous-batching
mode**: a slot-pooled ``ContinuousBatcher`` decode loop streaming
request waves at concurrency 1/4/8 (tokens/s per level lands in the
JSON under ``serve_continuous``).  CSV lines (the harness
format): ``name,us_per_call,derived``; the JSON summary (default
``BENCH_engine.json``) is stamped with the git SHA and the
encode-config metadata so the perf trajectory stays comparable PR over
PR.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

try:
    from benchmarks.common import Timer, bench_meta, csv_line
except ImportError:                                   # run as a script
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import Timer, bench_meta, csv_line

import repro.api as codr


def build(small: bool) -> tuple[codr.CompiledModel, tuple[int, int]]:
    """conv → conv → linear compiled model on paper-CNN channel
    geometry, encoded once under the benchmark's EncodeConfig."""
    rng = np.random.default_rng(0)
    if small:
        spec = codr.ModelSpec.from_paper_cnn("vgg16", n_conv=2, ri=20,
                                             ci=20, n_out=10, density=0.4,
                                             rng=rng)
        hw = (20, 20)
    else:
        spec = codr.ModelSpec.from_paper_cnn("alexnet", n_conv=2, ri=67,
                                             ci=67, n_out=100, density=0.4,
                                             rng=rng)
        hw = (67, 67)
    # the real bitstream decode path — the vectorized bulk decoder makes
    # it cheap enough to benchmark end-to-end
    config = codr.EncodeConfig(decode_source="bitstream")
    return codr.compile(spec, config), hw


def main(small: bool = False, batch: int = 8, iters: int = 5,
         json_path: str | None = "BENCH_engine.json") -> dict:
    compiled, hw = build(small)
    model = compiled.model
    rng = np.random.default_rng(1)
    n_in = model.layers[0].code.shape[1]
    x = rng.normal(size=(batch, *hw, n_in)).astype(np.float32)

    with Timer() as t_dec:                     # offline bitstream decode
        for layer in model.layers:             # (bulk decoder, once ever)
            _ = layer.tiles
    with Timer() as t_compile:                 # compile + first dispatch
        np.asarray(compiled.run(x))

    with Timer() as t_run:                     # steady state (post-compile)
        for _ in range(iters):
            y = compiled.run(x)
        y.block_until_ready()
    us = t_run.dt / iters * 1e6
    imgs_s = batch * iters / t_run.dt
    print(csv_line("engine_decode", t_dec.dt * 1e6,
                   f"bits={compiled.total_bits()};"
                   f"decode_s={t_dec.dt:.4f}"))
    print(csv_line("engine_compile", t_compile.dt * 1e6,
                   f"traces={compiled.trace_count}"))
    print(csv_line("engine_forward", us,
                   f"imgs_per_s={imgs_s:.1f};batch={batch};"
                   f"bits_per_weight={compiled.bits_per_weight():.2f};"
                   f"steady_state=post_compile"))

    # sharded tile-parallel executor (same compiled model, backend
    # override; 1-element mesh = the single-device fallback)
    import jax
    n_dev = len(jax.devices())
    np.asarray(compiled.run(x, backend="sharded"))   # compile + shard once
    with Timer() as t_shard:
        for _ in range(iters):
            y_sh = compiled.run(x, backend="sharded")
        y_sh.block_until_ready()
    us_shard = t_shard.dt / iters * 1e6
    print(csv_line("engine_forward_sharded", us_shard,
                   f"imgs_per_s={batch * iters / t_shard.dt:.1f};"
                   f"devices={n_dev};batch={batch}"))

    server = compiled.serve(max_batch=batch)
    samples = [rng.normal(size=(*hw, n_in)).astype(np.float32)
               for _ in range(batch + 3)]
    server.serve(samples)                      # warm the size buckets
    batches_before = server.batches_run
    with Timer() as t_srv:
        outs = server.serve(samples)
    print(csv_line("engine_serve", t_srv.dt / len(outs) * 1e6,
                   f"requests={len(outs)};"
                   f"batches={server.batches_run - batches_before};"
                   f"buckets={len(server.bucket_counts)}"))

    # async futures path: background flush loop, max_batch load trigger,
    # double-buffered staging — same request stream as the sync server
    aserver = compiled.serve(max_batch=batch, flush_deadline_s=0.005)
    with aserver:
        [f.result() for f in [aserver.submit_async(s) for s in samples]]
        abatches_before = aserver.batches_run
        with Timer() as t_async:
            futs = [aserver.submit_async(s) for s in samples]
            outs_a = [f.result() for f in futs]
    print(csv_line("engine_serve_async", t_async.dt / len(outs_a) * 1e6,
                   f"requests={len(outs_a)};"
                   f"batches={aserver.batches_run - abatches_before};"
                   f"deadline_s={aserver.flush_deadline_s}"))

    # transformer serving from the packed representation: prefill +
    # greedy decode of an repro.models LM with every projection executing
    # through the decode-fused codr_matmul backend (interpret mode on
    # CPU), HBM bytes measured on the stored pack
    from repro.configs import get_config, smoke_variant
    from repro.launch.serve import run_serve
    st = run_serve(smoke_variant(get_config("qwen2.5-3b")), batch=2,
                   prompt_len=4 if small else 8,
                   gen_len=4 if small else 16,
                   use_codr=True, verbose=False)
    print(csv_line("engine_serve_transformer", st["ms_per_tok"] * 1e3,
                   f"arch={st['arch']};backend={st['backend']};"
                   f"hbm_bytes={st['hbm_bytes']};"
                   f"kv_bytes={st['kv_bytes']};"
                   f"bits_per_weight={st['bits_per_weight']:.2f}"))

    # continuous batching over the same packed representation: one
    # ContinuousBatcher (8 KV-cache slots, compiled once) streams
    # request waves at concurrency 1 / 4 / 8 — tokens/s should scale
    # with concurrency because every pooled decode step amortizes one
    # packed weight fetch over all active slots
    import jax as _jax
    from repro.core.batching import ContinuousBatcher
    from repro.models import get_model

    cb_cfg = smoke_variant(get_config("qwen2.5-3b"))
    cb_api = get_model(cb_cfg)
    cb_params = cb_api.init_params(_jax.random.PRNGKey(0), cb_cfg)
    cb_compiled = codr.compile_params(
        cb_params, codr.EncodeConfig(n_unique=16), backend="codr_matmul")
    cb_prompt_len = 4 if small else 8
    cb_gen = 4 if small else 8
    batcher = ContinuousBatcher(cb_compiled, cb_cfg, n_slots=8,
                                max_len=cb_prompt_len + cb_gen)
    prng = np.random.default_rng(2)
    def _wave(n):
        prompts = [prng.integers(0, cb_cfg.vocab_size, size=cb_prompt_len)
                   for _ in range(n)]
        hs = [batcher.submit(p, max_new_tokens=cb_gen) for p in prompts]
        return sum(len(h.result(timeout=600)) for h in hs)
    _wave(8)                                   # warm prefill + step jits
    conc_toks_s: dict[str, float] = {}
    cb_kv_bytes = batcher.kv_bytes()
    for conc in (1, 4, 8):
        with Timer() as t_cb:
            n_toks = _wave(conc)
        conc_toks_s[str(conc)] = n_toks / t_cb.dt
        print(csv_line(f"engine_serve_continuous_c{conc}",
                       t_cb.dt / n_toks * 1e6,
                       f"arch={cb_cfg.name};backend=codr_matmul;"
                       f"n_slots=8;tokens={n_toks};"
                       f"kv_bytes={cb_kv_bytes};"
                       f"toks_per_s={conc_toks_s[str(conc)]:.1f}"))
    batcher.stop_async()
    # int8 paged pool on the same geometry — resident KV bytes are the
    # point of the quantized page pool, so record both side by side
    # (no worker is started; this only materializes the pool)
    cb_kv_bytes_int8 = ContinuousBatcher(
        cb_compiled, cb_cfg, n_slots=8, max_len=cb_prompt_len + cb_gen,
        kv_dtype="int8", kv_page_size=4).kv_bytes()
    print(csv_line("engine_kv_pool_int8", 0.0,
                   f"kv_bytes={cb_kv_bytes_int8};"
                   f"kv_bytes_bf16={cb_kv_bytes};"
                   f"ratio={cb_kv_bytes / max(cb_kv_bytes_int8, 1):.2f}"))

    # packed checkpoint artifact: compress-once/boot-many — save the
    # already-compiled transformer params and time the mmap reload
    import os as _os
    import shutil as _shutil
    import tempfile as _tempfile
    _ckdir = _tempfile.mkdtemp(prefix="codr_bench_")
    _ckpath = _os.path.join(_ckdir, "packed.codr")
    with Timer() as t_ck_save:
        codr.save_packed(cb_compiled, _ckpath)
    ck_disk_bytes = sum(
        _os.path.getsize(_os.path.join(_ckpath, f))
        for f in _os.listdir(_ckpath))
    with Timer() as t_ck_load:
        ck_loaded = codr.load_packed(_ckpath)
    assert len(ck_loaded.packed_paths) == len(cb_compiled.packed_paths)
    _shutil.rmtree(_ckdir)
    print(csv_line("engine_packed_boot", t_ck_load.dt * 1e6,
                   f"save_us={t_ck_save.dt * 1e6:.1f};"
                   f"disk_bytes={ck_disk_bytes};"
                   f"format_version={codr.CODR_FORMAT_VERSION}"))

    # latency under faults: the same async request path, clean vs with a
    # seeded fault plan (transient dispatch errors + injected latency)
    # absorbed by the retry policy — the p50/p95/p99 spread is the cost
    # of resilience actually exercised, not just installed
    import time as _time
    from repro.runtime import resilience as res

    n_fault_req = 12 if small else 24
    fx = [rng.normal(size=(*hw, n_in)).astype(np.float32)
          for _ in range(n_fault_req)]

    def _latencies(server):
        lats = []
        for s in fx:
            t0 = _time.perf_counter()
            server.submit_async(s).result(timeout=600)
            lats.append(_time.perf_counter() - t0)
        return np.asarray(lats)

    fault_stats: dict[str, dict] = {}
    for mode in ("clean", "injected"):
        fsrv = compiled.serve(max_batch=4, flush_deadline_s=0.002)
        inj = None
        if mode == "injected":
            plan = res.FaultPlan.seeded(
                0, (res.SITE_SERVER_DISPATCH,), n_faults=6,
                kinds=("error", "latency"), max_call=n_fault_req,
                latency_s=0.005)
            inj = res.FaultInjector(plan)
            fsrv.configure_resilience(
                injector=inj,
                retry_policy=res.RetryPolicy(max_retries=3,
                                             backoff_s=0.001))
        with fsrv:
            fsrv.submit_async(fx[0]).result(timeout=600)   # warm
            lats = _latencies(fsrv)
        p50, p95, p99 = np.percentile(lats, [50, 95, 99]) * 1e3
        fault_stats[mode] = {
            "p50_ms": float(p50), "p95_ms": float(p95),
            "p99_ms": float(p99),
            "faults_fired": len(inj.fired) if inj else 0,
        }
        print(csv_line(f"engine_serve_faults_{mode}",
                       float(np.mean(lats)) * 1e6,
                       f"requests={n_fault_req};p50_ms={p50:.3f};"
                       f"p95_ms={p95:.3f};p99_ms={p99:.3f};"
                       f"faults={fault_stats[mode]['faults_fired']}"))

    for name, acc in compiled.sram_report(hw):
        print(csv_line(f"engine_sram_{name}", 0.0,
                       f"total_sram={acc.total_sram:.0f};"
                       f"feature_sram={acc.feature_sram:.0f};"
                       f"weight_rows={acc.weight_sram_rows:.0f}"))

    result = {
        "benchmark": "engine", "small": small, "batch": batch,
        "meta": bench_meta(encode_config=compiled.config.metadata(),
                           backend=compiled.backend.name),
        "decode_s": t_dec.dt,
        "compile_s": t_compile.dt,
        "steady_us_per_call": us,
        "imgs_per_s": imgs_s,
        "sharded_us_per_call": us_shard,
        "sharded_imgs_per_s": batch * iters / t_shard.dt,
        "n_devices": n_dev,
        "serve_us_per_request": t_srv.dt / len(outs) * 1e6,
        "serve_async_us_per_request": t_async.dt / len(outs_a) * 1e6,
        "serve_transformer": {
            "arch": st["arch"], "backend": st["backend"],
            "ms_per_tok": st["ms_per_tok"],
            "prefill_s": st["prefill_s"],
            "hbm_bytes": st["hbm_bytes"],
            "kv_bytes": st["kv_bytes"],
            "dense_bf16_bytes": st["dense_bf16_bytes"],
            "bits_per_weight": st["bits_per_weight"],
            "n_packed_tensors": st["n_packed"],
        },
        "serve_continuous": {
            "arch": cb_cfg.name, "backend": "codr_matmul",
            "n_slots": 8, "prompt_len": cb_prompt_len, "gen_len": cb_gen,
            "concurrency_tokens_per_s": conc_toks_s,
            "kv_bytes": cb_kv_bytes,
            "kv_bytes_int8_paged": cb_kv_bytes_int8,
        },
        "packed_boot": {
            "save_s": t_ck_save.dt, "load_s": t_ck_load.dt,
            "disk_bytes": ck_disk_bytes,
            "format_version": codr.CODR_FORMAT_VERSION,
        },
        "serve_faults": {
            "requests": n_fault_req,
            "retry_policy": {"max_retries": 3, "backoff_s": 0.001},
            **{m: s for m, s in fault_stats.items()},
        },
        "bits_per_weight": compiled.bits_per_weight(),
        "trace_count": compiled.trace_count,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2)
    return result


def cli(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="tiny model (CI smoke run)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--json", default="BENCH_engine.json",
                    help="JSON output path ('' disables)")
    args = ap.parse_args(argv)
    if args.batch < 1 or args.iters < 1:
        ap.error("--batch and --iters must be >= 1")
    print("name,us_per_call,derived")
    main(small=args.small, batch=args.batch, iters=args.iters,
         json_path=args.json or None)


if __name__ == "__main__":
    cli()
