"""A packed mixture-of-experts LM with latent attention served by
``ContinuousBatcher``: ``lm_batcher``'s run (packs made on the device
from the seed, every prompt length warmed up, the mix sent through
``submit`` for the window, the served logits rows replayed through the
plain reference beside the configuration), for a DeepSeek-V2 layout.

Beside the requests it hands the metric readers the batcher's
``experts_touched`` records: for each prefill and pooled decode step,
when it came back, its token rows and how many (layer, expert) pairs
were given rows, so a reader can count the packed words the grouped
expert kernel had to read.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import harness, traffic
from bench.lib.harness import Outcome
from bench.runners import lm_batcher


def model_config(c: dict):
    """The program's ``ModelConfig`` for a DeepSeek-V2 configuration
    file.  Softmax over the experts with the top k renormalised equals a
    softmax over the top k logits; without, the top k are scaled."""
    from repro.configs.base import ModelConfig, YarnScaling
    if c["topk_method"] != "greedy" or c["scoring_func"] != "softmax":
        raise NotImplementedError(
            f"routing {c['topk_method']!r}/{c['scoring_func']!r}")
    rs = c["rope_scaling"]
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tied_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        use_mla=True, q_lora_rank=c["q_lora_rank"] or 0,
        kv_lora_rank=c["kv_lora_rank"], nope_head_dim=c["qk_nope_head_dim"],
        rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        n_experts=c["n_routed_experts"],
        n_shared_experts=c["n_shared_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        moe_every=c["moe_layer_freq"],
        n_dense_layers=c["first_k_dense_replace"],
        moe_gating="topk_softmax" if c["norm_topk_prob"]
        else "softmax_topk",
        moe_routed_scale=float(c["routed_scaling_factor"]))


def model_numbers(c: dict) -> dict:
    """Sizes the metric readers count work with."""
    return {"n_layers": c["num_hidden_layers"],
            "n_moe_layers": c["num_hidden_layers"]
            - c["first_k_dense_replace"],
            "d_model": c["hidden_size"],
            "moe_d_ff": c["moe_intermediate_size"],
            "n_experts": c["n_routed_experts"],
            "top_k": c["num_experts_per_tok"],
            "bits": c["codr"]["bits"]}


def run(run: harness.Run) -> Outcome:
    import jax
    from repro.core.batching import ContinuousBatcher
    from bench.lib.lm_moe_packs import make_params

    c, mix = run.config, run.mix
    srv = mix["server"]
    cfg = model_config(c)
    t = time.monotonic()
    params = make_params(cfg, run.seed, bits=c["codr"]["bits"],
                         backend=c["codr"]["backend"])
    jax.block_until_ready(params)
    run.log(f"packs made in {time.monotonic() - t!r} s")
    t = time.monotonic()
    batcher = ContinuousBatcher(params, cfg, n_slots=srv["n_slots"],
                                max_len=srv["max_len"],
                                kv_dtype=c["kv_dtype"],
                                kv_page_size=c["kv_page_size"],
                                record_logits=True)
    # warm up: every prompt length of the mix through the window's path
    warm = [batcher.submit(np.zeros(n, np.int32), max_new_tokens=2)
            for n in traffic.prompt_lengths(mix)]
    for h in warm:
        h.result()
    run.log(f"batcher built and every prompt length warmed up in "
            f"{time.monotonic() - t!r} s")
    reqs = traffic.requests(mix, run.seed, run.seconds + (
        harness.TRACE_SECONDS if run.trace else 0.0), cfg.vocab_size)
    recs, prompts, threads = lm_batcher.serve(run, batcher, reqs)
    batcher.stop_async(drain=False)
    for t in threads:
        t.join(timeout=lm_batcher.DRAIN_S)
    run.log(f"every client stopped {time.monotonic() - run.end!r} s after "
            f"the traffic's end")
    peak = harness.peak_bytes()
    attempted = sum(1 for r in recs if run.t0 <= r.due < run.t1)
    failed = sum(1 for r in recs if r.status == "failed")
    numbers = dict(model_numbers(c),
                   experts_touched=list(batcher.experts_touched))
    sample = lm_batcher._sample(recs, run.seed, srv["max_len"])
    del batcher, params
    gc.collect()
    correct, checks = lm_batcher.judge(c, None)
    control = None
    if sample:
        t = time.monotonic()
        got = lm_batcher.check(c, run.seed, sample, prompts, srv["max_len"],
                               run.control)
        run.log(f"reference over {len(sample)} requests, "
                f"{sum(len(r.tokens) for r in sample)} served tokens: "
                f"{time.monotonic() - t!r} s; (max_logit_dev, widest gap, "
                f"tokens off the reference's best): {got}")
        correct, checks = lm_batcher.judge(c, got["served"][0])
        if run.control:
            control = lm_batcher.judge(c, got["control"][0])
    return Outcome(correct=correct, attempted=attempted, failed=failed,
                   checks=checks, memory_peak_bytes=peak, requests=recs,
                   model=numbers, wait_end=run.wait_end, control=control)
