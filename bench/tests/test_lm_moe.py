"""The DeepSeek-V2-Lite cell at a size a CPU test run can hold: its
packs against the reference's weights, whole runs sound and with the
model broken underneath, the control, and the grouped kernel's readers
on a small synthetic trace."""
import json
import time

import numpy as np
import pytest

from bench.lib import harness, lm_moe_trace, lm_weights, traffic, xtrace
from bench.tests import tiny

CELL = "lm_dsv2lite.chat"
MS = 1e6


def config():
    """The configuration's file with its widths cut down: one dense
    layer and two of experts, 64 experts of which each token takes 6
    (with 8, renormalising the top 6 moves the gates too little to
    tell)."""
    with open(harness.ROOT / "bench/configs/deepseek-v2-lite.json") as f:
        c = json.load(f)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, vocab_size=512, num_hidden_layers=3,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
             v_head_dim=16, moe_intermediate_size=32, n_routed_experts=64)
    return c


def mix():
    m = traffic.load("chat_dsv2lite")
    m.update(rate_per_s=4.0, ramp_s=0.5)
    m["prompt_len"]["grid"] = [8, 16, 32]
    m["output_len"].update(min=3, max=24, median=8)
    m["server"] = {"n_slots": 4, "max_len": 64}
    return m


def run(*, control=False, c=None):
    spec = tiny.spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    return harness.run_cell(
        spec, cell, seed=tiny.SEED, seconds=2.0, trace=False,
        t_start=time.monotonic(), clock=harness.CompileClock(),
        config=c or config(), mix=mix(), control=control)


def _ref():
    return harness.load_module(
        harness.BENCH_DIR / "configs" / "deepseek-v2-lite.reference.py",
        "dsv2_reference")


def _same(a, b):
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)


def test_packs_are_the_references_weights():
    """The served packs decode to the weights the reference rebuilds:
    an expert of a layer, the router, the dense layer's FFN (to a
    rounding of the per-leaf scale, computed inside and outside a jit)."""
    from bench.lib.lm_moe_packs import make_params
    from bench.runners.lm_moe_batcher import model_config
    c = config()
    cfg = model_config(c)
    params = make_params(cfg, tiny.SEED, bits=4)
    ref = _ref()
    key = lm_weights.seed_key(tiny.SEED)
    ch = ref._hashable(c)
    mlp = params["stack"]["b0"]["mlp"]
    gate, _, down = ref.expert_weights(key, 1, c=ch, bits=4)
    _same(
        np.asarray(mlp["w_experts_gate"].dense()[1, 5]), np.asarray(gate[5]))
    _same(
        np.asarray(mlp["w_experts_out"].dense()[1, 3]), np.asarray(down[3]))
    w = ref.layer_weights(key, 0, c=ch, bits=4, dense=False)
    _same(np.asarray(mlp["router"].dense()[0]),
                                  np.asarray(w["router"]))
    w0 = ref.layer_weights(key, 0, c=ch, bits=4, dense=True)
    _same(
        np.asarray(params["prologue"][0]["mlp"]["down_proj"].dense()),
        np.asarray(w0["down_proj"]))


def test_cell_sound_is_correct_and_control_is_not():
    """A whole tiny run through the batcher (int8 latent pages,
    ``codr_matmul`` and ``codr_gmm`` packs): served logits agree with
    the reference; the reference in float8 put in the program's place
    does not.  Besides the cell's limit, the tiny run's own reading is
    held under 0.5: bf16 activations and int8 pages put it at 0.20
    here (CPU), and an expert swapped on a near-tie moves it further."""
    r = run(control=True)
    assert r["correct"], r["checks"]
    assert r["checks"]["max_logit_dev"]["value"] < 0.5
    assert r["failed"] == 0 and r["attempted"] > 0
    assert not r["control"]["correct"], r["control"]
    assert set(r["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                 "tokens_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


def _wrong_gating(monkeypatch):
    from bench.runners import lm_moe_batcher
    make = lm_moe_batcher.model_config

    def topk_softmax(c):
        import dataclasses
        return dataclasses.replace(make(c), moe_gating="topk_softmax")
    monkeypatch.setattr(lm_moe_batcher, "model_config", topk_softmax)


def _no_shared_experts(monkeypatch):
    from bench.lib import lm_moe_packs
    make = lm_moe_packs.make_params

    def dropped(*a, **k):
        params = make(*a, **k)
        del params["stack"]["b0"]["mlp"]["shared"]
        return params
    monkeypatch.setattr(lm_moe_packs, "make_params", dropped)


@pytest.mark.parametrize("fault", [_wrong_gating, _no_shared_experts])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = run()
    assert not r["correct"], r["checks"]


def _op(name, start, dur):
    return xtrace.Event(name, start * MS, dur * MS)


def _trace():
    """A prefill of 32 tokens, then two decode steps of 4 slots, each
    with one codr_matmul call and two codr_gmm calls (K 64, N 32)."""
    mm = ("%codr_matmul_pallas.1 = f32[8,{m},8]{{2,1,0}} custom-call("
          "f32[{m},64]{{1,0}} %x, s32[64,8]{{1,0}} %w, f32[16] %t)")
    gmm = ("%codr_gmm.2 = f32[8,96,128]{2,1,0} custom-call(s32[8]{0} %a, "
           "s32[8]{0} %b, s32[8]{0} %c, s32[8]{0} %d, f32[96,64]{1,0} %x, "
           "s32[8,64,4]{2,1,0} %w, f32[8,16]{1,0} %t, f32[8]{0} %s)")
    ops, programs = [], []
    for i, (name, m) in enumerate([("jit_prefill(2)", 32),
                                   ("jit_decode_step(1)", 4),
                                   ("jit_decode_step(1)", 4)]):
        t = 10.0 * i
        programs.append(_op(name, t, 6))
        ops += [_op(mm.format(m=m), t + 0.5, 1), _op(gmm, t + 2, 1),
                _op(gmm, t + 4, 1)]
    return xtrace.Trace(ops=ops, programs=programs, host=[], n_devices=1,
                        window_s=0.030)


def test_gmm_calls_shapes_and_program_matching():
    tr = _trace()
    calls = lm_moe_trace.gmm_calls(tr)
    assert [(k, n, b) for _, k, n, b in calls] == [(64, 32, 4)] * 6
    # records: two earlier steps, then the traced prefill and steps
    recs = [(0.001, "step", 4, 9), (0.5, "step", 4, 9),
            (100.007, "prefill", 32, 14), (100.017, "step", 4, 11),
            (100.027, "step", 4, 10)]
    pairs = lm_moe_trace.matched_programs(tr, recs, 100.0)
    assert [r for _, r in pairs] == recs[2:]
    assert [len(c) for c, _ in pairs] == [2, 2, 2]
    assert lm_moe_trace.matched_programs(tr, recs[:2], 100.0) == []


def test_gmm_readers_by_hand():
    """Each call's least time: its rows (tokens x 6) and, as bytes, the
    words of its program's experts per MoE layer plus bf16 rows in and
    out; against 1 ms per call."""
    from bench.lib import peaks
    tr = _trace()
    recs = [(100.007, "prefill", 32, 14), (100.017, "step", 4, 11),
            (100.027, "step", 4, 10)]
    model = {"n_moe_layers": 2, "top_k": 6}
    ctx = harness.Ctx(cell={}, config={}, mix={}, seconds=51.0,
                      device_kind="TPU v5 lite", setup_s=1.0, t0=0.0,
                      t1=51.0, requests=[], calls=[],
                      model=dict(model, experts_touched=recs), trace=tr,
                      trace_t0=100.0)
    least = 0.0
    for _, _, tokens, touched in recs:
        flops = 2.0 * tokens * 6 * 64 * 32
        nbytes = touched / 2 * 64 * 32 * 0.5 + tokens * 6 * (64 + 32) * 2
        least += 2 * peaks.least_time_s(flops, nbytes, "TPU v5 lite")
    assert lm_moe_trace.gmm_roofline(ctx) == pytest.approx(
        100.0 * least / 0.006)
    # two decode runs, two 1 ms calls in each
    assert lm_moe_trace.gmm_ms_per_step(tr) == pytest.approx(2.0)
    # the chat cell's readers, which list this cell too
    for name in ("codr_gmm_roofline.lm_moe", "codr_gmm_ms_per_step.lm_moe",
                 "codr_matmul_roofline.lm_chat", "decode_gap_share.lm_chat"):
        assert harness.read_metric(name, ctx) is not None
        ctx_none = harness.Ctx(**{**ctx.__dict__, "trace": None})
        assert harness.read_metric(name, ctx_none) is None


def test_reference_yarn_closed_form():
    """The reference's YaRN, written apart from the program's, gives the
    published numbers: correction dims 10 and 23, softmax scale
    192**-0.5 * 1.58963."""
    ref = _ref()
    with open(harness.ROOT / "bench/configs/deepseek-v2-lite.json") as f:
        c = json.load(f)
    inv, cs, sm = ref.yarn(c)
    i = np.arange(32)
    extra = 1e4 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, extra / 40 * ramp + extra * (1 - ramp),
                               rtol=1e-12)
    assert cs == 1.0
    assert sm == pytest.approx(192 ** -0.5 * 1.58963, rel=5e-5)


def test_moe_cost_counts_experts_given_rows():
    from bench.costs import codr_gmm
    flops, nbytes = codr_gmm.work(384, 2048, 1408, 4, 63.0)
    assert flops == 2.0 * 384 * 2048 * 1408
    assert nbytes == 63.0 * 2048 * 1408 / 2 + 384 * (2048 + 1408) * 2


def test_mix_has_the_chat_cells_lengths():
    """The cell's traffic is the chat cell's lengths and arrivals shape
    at its own rate, over 64 slots (MLA's 15.5 KB per token lets them
    fit), so the two architectures read side by side."""
    chat, moe = traffic.load("chat"), traffic.load("chat_dsv2lite")
    assert moe["prompt_len"] == chat["prompt_len"]
    assert moe["output_len"] == chat["output_len"]
    assert moe["server"] == {"n_slots": 64, "max_len": 1280}
    reqs = traffic.requests(moe, tiny.SEED, 51.0, 102400)
    assert len(reqs) == traffic.n_requests(moe, 51.0) > 30
    assert max(len(q.prompt) + q.max_new_tokens for q in reqs) <= 1280


def test_mix_ramps_as_the_chat_cell():
    """The window opens 5 s after the first arrivals, as the chat
    cell's does, and the mix states its rate as a number."""
    chat, moe = traffic.load("chat"), traffic.load("chat_dsv2lite")
    assert moe["ramp_s"] == chat["ramp_s"] == 5.0
    assert moe["kind"] == "open_loop"
    assert isinstance(moe["rate_per_s"], float) and moe["rate_per_s"] > 0


def test_reference_routes_each_token_to_its_experts():
    """The reference's routed experts, gathered per expert and gathered
    back per token, equal each token's top 6 experts' SwiGLU weighted by
    their softmax probabilities (not renormalised), computed token by
    token; the replay's programs hold no scatter."""
    import jax
    import jax.numpy as jnp
    ref = _ref()
    c = config()
    n, d, f, e, k = 40, 8, 4, 64, 6
    g = np.random.default_rng(3)
    a = g.standard_normal((n, d)).astype(np.float32)
    probs = jax.nn.softmax(jnp.asarray(g.standard_normal((n, e)),
                                       jnp.float32), axis=-1)
    ew = tuple(jnp.asarray(g.standard_normal(s), jnp.float32)
               for s in ((e, d, f), (e, d, f), (e, f, d)))
    got, margin = ref.moe_ffn(jnp.asarray(a), probs, ew, c=c, fp8=False)
    p = np.asarray(probs, np.float64)
    wg, wu, wd = (np.asarray(w, np.float64) for w in ew)
    want = np.zeros((n, d))
    for t in range(n):
        for x in np.argsort(-p[t])[:k]:
            h = a[t] @ wg[x]
            h = h / (1 + np.exp(-h)) * (a[t] @ wu[x])
            want[t] += p[t, x] * (h @ wd[x])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4)
    srt = np.sort(p, axis=1)[:, ::-1]
    assert margin == pytest.approx((srt[:, k - 1] - srt[:, k]).min(),
                                   rel=1e-5)
    rows = jnp.zeros((e, 256), jnp.int32)
    slots = jnp.zeros((n, k), jnp.int32)
    hlo = [ref.routed.lower(jnp.asarray(a), rows, slots,
                            jnp.zeros((n, k)), *ew, fp8=False).as_text(),
           ref.add_rows.lower(jnp.zeros((n + 3, d)),
                              jnp.zeros(n + 3, jnp.int32),
                              jnp.asarray(a), jnp.asarray(a)).as_text()]
    assert not any("scatter" in h for h in hlo)
