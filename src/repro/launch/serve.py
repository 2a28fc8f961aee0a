"""Serving driver: batched prefill + decode with CoDR-compressed weights.

Demonstrates the paper's technique as a first-class serving feature:
``--codr`` compiles the params pytree onto the packed bitstream
representation (``repro.api.compile_params``) so every projection matmul
resolves through the backend registry into the decode-fused
``codr_matmul`` kernel (interpret mode on CPU, Mosaic on TPU) — the
model serves *from* the compressed weights, not from a dense copy that
merely had quantization applied — and the reported weight HBM bytes are
measured on the stored pack rather than estimated.

``--packed-ckpt [PATH]`` boots from a packed checkpoint artifact
(``repro.api.save_packed``): if PATH exists it is mmap-loaded (no
re-encode); otherwise the run compiles once, saves the artifact, and
reloads it — so the flag is self-contained in CI.  Packed boots default
to the quantized **paged** KV cache (``--kv-dtype int8``); ``--kv-dtype
bf16`` with ``--kv-page-size`` gives the bit-identical paged escape
hatch, and ``--check`` verifies each mode against the dense-cache
sequential reference (token-exact for bf16, teacher-forced logit bound
for int8 — docs/DESIGN.md §2.2).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.api as codr
from repro.configs import ModelConfig, get_config, smoke_variant
from repro.core.serving import codr_serving_stats
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model


def run_serve(cfg: ModelConfig, *, batch: int = 4, prompt_len: int = 32,
              gen_len: int = 32, use_codr: bool = False,
              codr_unique: int = 16, codr_backend: str = "codr_matmul",
              verbose: bool = True) -> dict:
    """One serving run of ``cfg``: prefill + greedy decode.  Returns a
    metrics dict (timings, generated tokens, and — under ``use_codr`` —
    the measured packed-representation HBM bytes).  Importable so tests,
    benchmarks, and CI drive the same path as the CLI."""
    api = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = api.init_params(key, cfg)

    compiled = None
    if use_codr:
        compiled = codr.compile_params(
            params, codr.EncodeConfig(n_unique=codr_unique),
            backend=codr_backend)
        params = compiled.params
        if verbose:
            print(compiled.summary())

    total = prompt_len + gen_len
    tokens = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    batch_in = {"tokens": tokens}
    if cfg.frontend or cfg.family == "encdec":
        batch_in["prefix"] = jax.random.normal(
            key, (batch, cfg.frontend_seq, cfg.d_model))

    t0 = time.monotonic()
    logits, cache = api.prefill(params, batch_in, cfg)
    t_prefill = time.monotonic() - t0

    step = jax.jit(lambda p, c, t, i: api.decode_step(p, c, t, i, cfg))
    out_tokens: list[np.ndarray] = []
    cache_self_len = None
    n_steps = 0                      # decode_step calls actually executed
    t0 = time.monotonic()
    if cfg.family == "encdec":
        # Continue from the prefill cache: pad the decoder self-attention
        # KV out to the full prompt+gen length (decode writes positions
        # >= prompt_len; the tail stays masked until written).  The
        # cross-attention KV carries the encoder output and must be kept
        # — re-initializing it (the old replay path) served decode steps
        # against an all-zero encoder.
        pad = total - cache["self"][0].shape[2]
        if pad > 0:
            cache = {**cache, "self": tuple(
                jnp.pad(kv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                for kv in cache["self"])}
        cache_self_len = int(cache["self"][0].shape[2])
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        if gen_len > 0:
            out_tokens.append(np.asarray(tok))
        for i in range(prompt_len, total - 1):
            logits, cache = step(params, cache, tok, jnp.int32(i))
            n_steps += 1
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out_tokens.append(np.asarray(tok))
    else:
        # greedy decode continuing from a fresh full-length cache: replay
        # the prompt then generate (keeps cache shapes static)
        cache = api.init_cache(cfg, batch, total)
        tok = tokens[:, 0]
        for i in range(total - 1):
            logits, cache = step(params, cache, tok, jnp.int32(i))
            n_steps += 1
            if i + 1 < prompt_len:
                tok = tokens[:, i + 1]
            else:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out_tokens.append(np.asarray(tok))
    t_decode = time.monotonic() - t0
    gen = (np.stack(out_tokens, 1) if out_tokens
           else np.zeros((batch, 0), np.int32))

    # per executed decode_step call — the LM path replays the prompt
    # through decode, so dividing by generated tokens alone would
    # overstate the per-token cost
    ms_per_tok = t_decode / max(n_steps, 1) * 1e3
    kv_bytes = sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(cache))
    if verbose:
        print(f"prefill {prompt_len} toks: {t_prefill*1e3:.1f} ms; "
              f"decode {n_steps} steps ({len(out_tokens)} generated): "
              f"{t_decode*1e3:.1f} ms ({ms_per_tok:.2f} ms/step)")
        if gen.size:
            print("sample generation (first row):", gen[0][:16])

    result = {
        "arch": cfg.name, "family": cfg.family, "gen": gen,
        "prefill_s": t_prefill, "decode_s": t_decode,
        "n_decode_steps": n_steps,
        "ms_per_tok": ms_per_tok,
        "cache_self_len": cache_self_len,
        "kv_bytes": kv_bytes,
    }
    if compiled is not None:
        # measured on the stored packed representation, not estimated
        result.update(
            hbm_bytes=compiled.hbm_bytes(),
            dense_bf16_bytes=compiled.dense_bf16_bytes(),
            bits_per_weight=compiled.bits_per_weight(),
            n_packed=len(compiled.packed_paths),
            backend=compiled.backend)
        if verbose:
            print(f"weight HBM, measured on the packed representation "
                  f"({compiled.backend}): "
                  f"{compiled.hbm_bytes()/1e6:.3f} MB vs "
                  f"bf16 {compiled.dense_bf16_bytes()/1e6:.3f} MB "
                  f"({compiled.compression_vs_bf16():.1f}x, "
                  f"{compiled.bits_per_weight():.2f} bits/weight)")
    elif verbose:
        stats = codr_serving_stats(cfg, n_unique=codr_unique)
        unit, scale = ("GB", 1.0) if stats["bf16_gb"] > 0.5 else ("MB", 1e3)
        print(f"decode HBM weight traffic/token ({stats['source']}: "
              f"extrapolated from one synthetic matrix, NOT measured — "
              f"full {cfg.name} geometry): "
              f"bf16={stats['bf16_gb']*scale:.2f} {unit}, "
              f"int8={stats['int8_gb']*scale:.2f} {unit}, "
              f"codr(U={codr_unique})≈{stats['codr_gb']*scale:.2f} {unit} "
              f"({stats['codr_bits_per_weight']:.2f} bits/weight)")
    return result


def run_serve_continuous(cfg: ModelConfig, *, n_requests: int = 4,
                         n_slots: int = 4, prompt_len: int = 8,
                         gen_len: int = 8, max_len: int = 64,
                         use_codr: bool = False, codr_unique: int = 16,
                         codr_backend: str = "codr_matmul",
                         check: bool = False, seed: int = 0,
                         chaos_seed: int | None = None,
                         kv_dtype: str | None = None,
                         kv_page_size: int | None = None,
                         packed_ckpt: str | None = None,
                         verbose: bool = True) -> dict:
    """Continuous-batching serving run of ``cfg``: ``n_requests``
    mixed-length prompts streamed through a :class:`repro.core.batching
    .ContinuousBatcher` slot pool.  With ``check=True`` every streamed
    output is asserted bit-identical to the sequential solo-decode
    reference on the same params (the CI smoke contract); lossy KV
    modes (``kv_dtype="int8"``) additionally replay the dense-cache
    reference's tokens teacher-forced through the paged pipeline and
    bound the per-step logit deviation.

    ``packed_ckpt`` boots the weights from a packed checkpoint
    artifact (saving one first if the path does not exist) and — unless
    overridden — turns on the quantized paged KV cache, so one flag
    exercises the full "compress offline, serve packed" path.

    ``chaos_seed`` arms a deterministic fault plan
    (:meth:`repro.runtime.resilience.FaultPlan.seeded` over the
    batcher's worker/prefill/decode sites: transient dispatch errors,
    injected latency, worker crashes) with retry + supervised-restart
    budgets sized to the plan — the chaos contract is that every
    request still finishes with bit-identical outputs, which
    ``--chaos <seed> --check`` asserts in CI."""
    from repro.core.batching import ContinuousBatcher

    api = get_model(cfg)
    key = jax.random.PRNGKey(seed)

    if kv_dtype is None:
        # packed boots default to the quantized paged cache; plain runs
        # keep today's dense bf16 pool
        kv_dtype = "int8" if packed_ckpt is not None else "bf16"
    if kv_dtype == "int8" and kv_page_size is None:
        kv_page_size = 4 if max_len <= 128 else 16

    compiled = None
    boot_s = encode_s = None
    if packed_ckpt is not None:
        import os
        if not os.path.exists(packed_ckpt):
            # self-contained: compile once and persist the artifact,
            # then boot from it like any later run would
            params = api.init_params(key, cfg)
            t0 = time.monotonic()
            cp = codr.compile_params(
                params, codr.EncodeConfig(n_unique=codr_unique),
                backend=codr_backend)
            codr.save_packed(cp, packed_ckpt)
            if verbose:
                print(f"packed checkpoint written to {packed_ckpt} "
                      f"({time.monotonic()-t0:.2f}s compile+save)")
        t0 = time.monotonic()
        compiled = codr.load_packed(packed_ckpt)
        boot_s = time.monotonic() - t0
        params = compiled.params
        if verbose:
            print(f"booted from packed checkpoint {packed_ckpt} in "
                  f"{boot_s*1e3:.1f} ms (format v"
                  f"{codr.CODR_FORMAT_VERSION}, mmap)")
            print(compiled.summary())
    else:
        params = api.init_params(key, cfg)
        if use_codr:
            t0 = time.monotonic()
            compiled = codr.compile_params(
                params, codr.EncodeConfig(n_unique=codr_unique),
                backend=codr_backend)
            encode_s = time.monotonic() - t0
            params = compiled.params
            if verbose:
                print(compiled.summary())
                print(f"encode (set-up, host): {encode_s:.2f} s")

    rng = np.random.default_rng(seed)
    # mixed prompt lengths around prompt_len: the join-on-prefill path
    # must handle ragged admissions
    lens = [max(1, prompt_len + (i % 3) - 1) for i in range(n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    max_len = max(max_len, max(lens) + gen_len)    # pool must fit every req

    batcher = ContinuousBatcher(params, cfg, n_slots=n_slots,
                                max_len=max_len, kv_dtype=kv_dtype,
                                kv_page_size=kv_page_size)
    injector = None
    if chaos_seed is not None:
        from repro.runtime import resilience as res
        plan = res.FaultPlan.seeded(
            chaos_seed,
            (res.SITE_BATCHER_WORKER, res.SITE_BATCHER_PREFILL,
             res.SITE_BATCHER_DECODE),
            n_faults=4, max_call=max(4, n_requests * gen_len // 2),
            latency_s=0.002)
        injector = res.FaultInjector(plan)
        # budgets sized to the plan: every injected fault is survivable,
        # so the run must finish with bit-identical outputs
        batcher.configure_resilience(
            injector=injector,
            retry_policy=res.RetryPolicy(max_retries=max(2, len(plan)),
                                         backoff_s=0.001),
            restart_policy=res.RestartPolicy(
                max_restarts=max(1, len(plan)), backoff_s=0.001))
        if verbose:
            print(f"chaos seed {chaos_seed}: {plan.describe()}")
    t0 = time.monotonic()
    handles = [batcher.submit(p, max_new_tokens=gen_len) for p in prompts]
    streamed = [[tok for tok in h] for h in handles]
    t_total = time.monotonic() - t0
    batcher.stop_async()

    n_tokens = sum(len(s) for s in streamed)
    toks_per_s = n_tokens / max(t_total, 1e-9)
    kv_bytes = batcher.kv_bytes()
    if verbose:
        print(f"continuous batching: {n_requests} requests "
              f"(prompt lens {lens}) over {n_slots} slots → "
              f"{n_tokens} tokens in {t_total*1e3:.1f} ms "
              f"({toks_per_s:.1f} tok/s); steps={batcher.steps_run} "
              f"prefills={batcher.prefills_run} "
              f"peak_active={batcher.peak_active}")
        print(f"KV pool: {kv_dtype}"
              + (f" paged (page_size={kv_page_size})"
                 if kv_page_size is not None else " dense")
              + f", {kv_bytes/1e3:.1f} kB resident")
        if injector is not None:
            print(f"chaos: {len(injector.fired)}/{len(injector.plan)} "
                  f"scheduled faults fired "
                  f"({[f'{f.site}#{f.at_call}:{f.kind}' for f in injector.fired]}); "
                  f"worker crashes={batcher.worker_crashes} "
                  f"restarts={batcher.worker_restarts}")
        if compiled is not None:
            stats = codr_serving_stats(cfg, reports=compiled.reports)
            print(f"weight HBM ({stats['source']} on this model's "
                  f"tensors): {compiled.hbm_bytes()/1e6:.3f} MB packed, "
                  f"{stats['pack_bits_per_weight']:.2f} pack bits/weight")

    matched = None
    check_dev = None
    if check:
        matched = 0
        # a dense-cache twin on the SAME served params is the oracle for
        # paged modes: bf16-paged must reproduce its tokens bit-exactly;
        # int8 is lossy, so its contract is the teacher-forced logit
        # bound (free-running greedy legitimately diverges on near-tied
        # logits — see ContinuousBatcher.replay_logits)
        dense_ref = (ContinuousBatcher(params, cfg, n_slots=n_slots,
                                       max_len=max_len)
                     if kv_page_size is not None else batcher)
        for p, s in zip(prompts, streamed):
            same, _ = batcher.generate_reference(p, max_new_tokens=gen_len)
            assert s == same, (
                f"streamed output diverged from the sequential reference:"
                f" {s} vs {same}")
            dense_toks, _ = dense_ref.generate_reference(
                p, max_new_tokens=gen_len)
            if kv_dtype == "int8":
                dense_rows = dense_ref.replay_logits(p, dense_toks)
                paged_rows = batcher.replay_logits(p, dense_toks)
                assert np.array_equal(paged_rows[0], dense_rows[0]), (
                    "prefill logits must be bit-exact across KV modes")
                spread = float(dense_rows.max() - dense_rows.min()) or 1.0
                dev = float(np.abs(paged_rows - dense_rows).max()) / spread
                check_dev = max(check_dev or 0.0, dev)
                assert dev < 0.10, (
                    f"int8-paged teacher-forced logits deviate "
                    f"{dev:.4f} of the dense logit spread (bound 0.10)")
            else:
                assert s == dense_toks, (
                    f"bf16 KV must match the dense-cache reference "
                    f"bit-exactly: {s} vs {dense_toks}")
            matched += 1
        if verbose:
            print(f"check: {matched}/{n_requests} streamed outputs "
                  f"verified against the dense-cache sequential "
                  f"reference"
                  + (f" (worst teacher-forced logit deviation "
                     f"{check_dev:.4f} of spread, bound 0.10)"
                     if check_dev is not None else " (bit-identical)"))

    return {
        "arch": cfg.name, "n_requests": n_requests, "n_slots": n_slots,
        "prompt_lens": lens, "gen": streamed, "total_s": t_total,
        "tokens_per_s": toks_per_s, "steps_run": batcher.steps_run,
        "prefills_run": batcher.prefills_run,
        "peak_active": batcher.peak_active, "checked": matched,
        "backend": compiled.backend if compiled is not None else None,
        "chaos_seed": chaos_seed,
        "faults_fired": (len(injector.fired) if injector is not None
                         else None),
        "worker_restarts": batcher.worker_restarts,
        "kv_dtype": kv_dtype, "kv_page_size": kv_page_size,
        "kv_bytes": kv_bytes, "boot_s": boot_s, "encode_s": encode_s,
        "packed_ckpt": packed_ckpt, "check_dev": check_dev,
        "compiled": compiled, "batcher": batcher,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--codr", action="store_true",
                    help="serve from the packed CoDR weight representation")
    ap.add_argument("--codr-unique", type=int, default=16,
                    help="unique-weight budget per tensor (paper Fig. 6 U)")
    ap.add_argument("--codr-backend", default="codr_matmul",
                    help="packed-matmul backend: codr_matmul (fused "
                         "decode+matmul kernel) or tiled/sharded "
                         "(decode-then-matmul reference lane)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching mode: stream --requests "
                         "concurrent mixed-length prompts through a "
                         "slot-pooled decode loop")
    ap.add_argument("--requests", type=int, default=4,
                    help="concurrent requests (--continuous)")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache pool slots (--continuous)")
    ap.add_argument("--check", action="store_true",
                    help="assert streamed outputs are bit-identical to "
                         "the sequential reference (--continuous)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject a deterministic seeded fault plan "
                         "(dispatch errors, latency, worker crashes) "
                         "into the continuous-batching run; combine "
                         "with --check to assert outputs survive "
                         "bit-identically (--continuous)")
    ap.add_argument("--packed-ckpt", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="boot from a packed checkpoint artifact "
                         "(codr.save_packed); writes one first if PATH "
                         "is missing.  Without PATH a per-arch default "
                         "under /tmp is used.  Implies --kv-dtype int8 "
                         "unless overridden (--continuous)")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"), default=None,
                    help="KV cache storage: bf16 (bit-identical; dense "
                         "unless --kv-page-size) or int8 (quantized "
                         "paged) (--continuous)")
    ap.add_argument("--kv-page-size", type=int, default=None,
                    help="tokens per KV page; enables the paged pool "
                         "for bf16 too (--continuous)")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = smoke_variant(get_config(args.arch))
    packed_ckpt = args.packed_ckpt
    if packed_ckpt == "":
        packed_ckpt = f"/tmp/codr_packed_{args.arch.replace('/', '_')}.codr"
    if args.continuous:
        run_serve_continuous(
            cfg, n_requests=args.requests, n_slots=args.slots,
            prompt_len=args.prompt_len, gen_len=args.gen_len,
            use_codr=args.codr, codr_unique=args.codr_unique,
            codr_backend=args.codr_backend, check=args.check,
            chaos_seed=args.chaos, kv_dtype=args.kv_dtype,
            kv_page_size=args.kv_page_size, packed_ckpt=packed_ckpt)
    else:
        run_serve(cfg, batch=args.batch,
                  prompt_len=args.prompt_len, gen_len=args.gen_len,
                  use_codr=args.codr, codr_unique=args.codr_unique,
                  codr_backend=args.codr_backend)


if __name__ == "__main__":
    main()
