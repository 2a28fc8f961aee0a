"""A packed decoder LM served by ``ContinuousBatcher``: packs made on the
device from the seed, every prompt length of the mix warmed up, then the
mix's requests sent through ``submit`` for the window, each streamed to
a client thread that stamps every token as it arrives.

The batcher records the logits row behind every token it serves
(``record_logits``, its own witness option: one host copy of each row).
Afterwards a sample of finished requests, drawn from the seed and with
the longest among them, is replayed through the plain reference beside
the configuration, teacher-forced on the served tokens: the largest
deviation of a served row from the reference's row at its position,
each row about its own mean, in units of the spread (standard
deviation) of the reference logits, is the number compared.  The widest gap by which a served token's logit
lies below the reference's best is logged beside it.
"""
from __future__ import annotations

import gc
import threading
import time
from concurrent import futures

import numpy as np

from bench.lib import harness, traffic
from bench.lib.harness import Outcome, RequestRecord

DRAIN_S = 60.0            # how long late answers are waited for
REF_TOKENS = 16 * 1024   # prompt and served tokens the check replays


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    hd = c["hidden_size"] // c["num_attention_heads"]
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=hd,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=c["attention_bias"],
        tied_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]))


def model_numbers(c: dict) -> dict:
    """Sizes the metric readers count work with."""
    d, f = c["hidden_size"], c["intermediate_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // hq
    proj = [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
            (d, f), (d, f), (f, d)]
    return {"n_layers": c["num_hidden_layers"], "d_model": d,
            "n_heads": hq, "head_dim": hd, "vocab": c["vocab_size"],
            "proj_shapes": proj, "bits": c["codr"]["bits"],
            "layer_params": sum(k * n for k, n in proj),
            "embed_params": c["vocab_size"] * d}


def _consume(rec: RequestRecord, handle) -> None:
    rec.logits = handle.logits
    try:
        for tok in handle:
            rec.times.append(time.monotonic())
            rec.tokens.append(tok)
        rec.status = "done"
    except futures.CancelledError:
        rec.status = "cancelled"
    except Exception:        # noqa: BLE001 — a failed request is counted
        rec.status = "failed"


def _start(batcher, req, rec, threads) -> None:
    rec.submitted = time.monotonic()
    try:
        handle = batcher.submit(req.prompt,
                                max_new_tokens=req.max_new_tokens)
    except Exception:        # noqa: BLE001 — a refused request is counted
        rec.status = "failed"
        return
    t = threading.Thread(target=_consume, args=(rec, handle), daemon=True)
    t.start()
    threads.append(t)


def _sample(recs, seed: int, max_len: int) -> list:
    """Finished requests to replay: the longest, then others drawn from
    the seed while their prompts and served tokens fit ``REF_TOKENS``."""
    done = [r for r in recs if r.status == "done" and r.tokens
            and len(r.logits) == len(r.tokens)
            and r.prompt_len + len(r.tokens) - 1 <= max_len]
    if not done:
        return []
    size = lambda r: r.prompt_len + len(r.tokens)
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    pick, n_tok = [longest], size(longest)
    for i in traffic.rng(seed, 3).permutation(len(rest)):
        if n_tok + size(rest[i]) > REF_TOKENS:
            continue
        pick.append(rest[i])
        n_tok += size(rest[i])
    return pick


def widest_gap(ref_rows, tokens) -> tuple[float, int]:
    """Largest amount by which a chosen token's reference logit lies
    below the reference's best logit at its position, in units of the
    spread of the reference logits; and how many chosen tokens are not
    the reference's best."""
    gaps = np.concatenate([rows.max(axis=1)
                           - rows[np.arange(len(tok)), np.asarray(tok)]
                           for rows, tok in zip(ref_rows, tokens)])
    spread = np.std(np.concatenate(ref_rows))
    return float(gaps.max() / spread), int(np.count_nonzero(gaps > 0))


def _centered(rows) -> np.ndarray:
    rows = np.asarray(rows, np.float32)
    return rows - rows.mean(axis=-1, keepdims=True)


def max_dev(ref_rows, rows) -> float:
    """Largest deviation of ``rows`` from the reference rows, each row
    taken about its own mean (a shift of a whole row changes no
    probability and no choice), in units of the spread of the centered
    reference logits."""
    dev = max(float(np.abs(_centered(a) - _centered(r)).max())
              for a, r in zip(rows, ref_rows))
    return dev / float(np.std(np.concatenate([_centered(r)
                                              for r in ref_rows])))


def check(config: dict, seed: int, sample, prompts, max_len: int,
          control: bool = False) -> dict:
    """``(max_logit_dev, widest gap, tokens off the reference's best)``
    of the served rows of ``sample`` (key ``served``); with ``control``,
    also of the control's rows put in their place (key ``control``: the
    reference with float8 e4m3 operands, one precision below the
    configuration's bfloat16 activations)."""
    ref = harness.load_module(
        harness.BENCH_DIR / "configs" / config["reference"], "lm_reference")
    seqs, pos = [], []
    for r in sample:
        p = prompts[id(r)]
        seqs.append(np.concatenate([p, np.asarray(r.tokens[:-1],
                                                   np.int32)]))
        pos.append(np.arange(len(p) - 1, len(p) - 1 + len(r.tokens)))
    bits = config["codr"]["bits"]
    rows = ref.logits_at(config, seed, seqs, pos, pad_to=max_len,
                         bits=bits)
    served = [np.stack(r.logits) for r in sample]
    out = {"served": (max_dev(rows, served),
                      *widest_gap(rows, [r.tokens for r in sample]))}
    if control:
        low = ref.logits_at(config, seed, seqs, pos, pad_to=max_len,
                            bits=bits, fp8=True)
        out["control"] = (max_dev(rows, low),
                          *widest_gap(rows, [lw.argmax(axis=1)
                                             for lw in low]))
    return out


def judge(config: dict, dev: float | None) -> tuple[bool, list]:
    """``(correct, checks)`` for a deviation from the reference (None:
    nothing finished that could be compared)."""
    limit = config["limits"]["max_logit_dev"]
    return dev is not None and dev <= limit, [("max_logit_dev", dev, limit)]


def serve(run, batcher, reqs):
    """Send ``reqs`` as the mix says and measure the window; returns the
    records and the prompts by record."""
    mix, recs, threads = run.mix, [], []
    prompts = {}
    for q in reqs:
        rec = RequestRecord(due=0.0, prompt_len=len(q.prompt),
                            max_new_tokens=q.max_new_tokens)
        recs.append(rec)
        prompts[id(rec)] = q.prompt
    run.end_setup()
    if mix["kind"] == "backlog":
        t_sub = time.monotonic()
        for q, rec in zip(reqs, recs):
            rec.due = t_sub
            _start(batcher, q, rec, threads)
        n_slots = mix["server"]["n_slots"]
        while batcher.active < n_slots:
            time.sleep(0.005)
        run.open_window()
        run.sleep_until(run.end)
    else:
        ramp = mix.get("ramp_s", 0.0)
        t0 = time.monotonic() + ramp + 0.05
        for q, rec in zip(reqs, recs):
            rec.due = t0 + q.due_s
        run.open_window(at=t0)

        def submitter():
            for q, rec in zip(reqs, recs):
                if rec.due >= run.end:
                    break
                delay = rec.due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                _start(batcher, q, rec, threads)

        sub = threading.Thread(target=submitter, daemon=True)
        sub.start()
        run.sleep_until(run.end)
        sub.join()
    run.close_window()
    # late answers: every request due in the window gets its first token
    deadline = time.monotonic() + DRAIN_S
    while time.monotonic() < deadline and any(
            r.submitted is not None and r.status == "pending"
            and not r.times and r.due < run.t1 for r in recs):
        time.sleep(0.01)
    run.wait_end = time.monotonic()
    late = [r.submitted - r.due for r in recs if r.submitted is not None
            and r.due >= run.t0]
    if late:
        run.log(f"generator lateness: mean {float(np.mean(late)) * 1e3!r} "
                f"ms, max {float(np.max(late)) * 1e3!r} ms over {len(late)} "
                f"sends")
    return recs, prompts, threads


def run(run: harness.Run) -> Outcome:
    import jax
    from repro.core.batching import ContinuousBatcher
    from bench.lib.lm_packs import make_params

    c, mix = run.config, run.mix
    srv = mix["server"]
    cfg = model_config(c)
    params = make_params(cfg, run.seed, bits=c["codr"]["bits"],
                         backend=c["codr"]["backend"])
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
    reqs = traffic.requests(mix, run.seed, run.seconds + (
        harness.TRACE_SECONDS if run.trace else 0.0), cfg.vocab_size)
    recs, prompts, threads = serve(run, batcher, reqs)
    batcher.stop_async(drain=False)
    for t in threads:
        t.join(timeout=DRAIN_S)
    peak = harness.peak_bytes()
    if mix["kind"] == "backlog":
        attempted = sum(1 for r in recs if r.times and r.times[0] <= run.t1)
    else:
        attempted = sum(1 for r in recs if run.t0 <= r.due < run.t1)
    failed = sum(1 for r in recs if r.status == "failed")
    sample = _sample(recs, run.seed, srv["max_len"])
    del batcher, params
    gc.collect()
    correct, checks = judge(c, None)
    control = None
    if sample:
        t = time.monotonic()
        got = check(c, run.seed, sample, prompts, srv["max_len"],
                    run.control)
        run.log(f"reference over {len(sample)} requests, "
                f"{sum(len(r.tokens) for r in sample)} served tokens: "
                f"{time.monotonic() - t!r} s; (max_logit_dev, widest gap, "
                f"tokens off the reference's best): {got}")
        correct, checks = judge(c, got["served"][0])
        if run.control:
            control = judge(c, got["control"][0])
    return Outcome(correct=correct, attempted=attempted, failed=failed,
                   checks=checks, memory_peak_bytes=peak, requests=recs,
                   model=model_numbers(c), wait_end=run.wait_end,
                   control=control)
