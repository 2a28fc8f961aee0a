"""DeepSeek-V2-Lite's mechanisms at a small size on the CPU: the config
and its parameter count, MLA without q-LoRA, YaRN against its closed
form, the two gating kinds against a hand computation, the experts'
counter, and packed expert stacks that stay packed in the served
decode step."""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core.api import EncodeConfig, compile_params
from repro.core.batching import ContinuousBatcher
from repro.models import get_model
from repro.models import moe as moe_mod
from repro.models.common import rope_freqs, softmax_scale
from repro.runtime import tracing

LITE = "deepseek-v2-lite"


@pytest.fixture(scope="module")
def lite():
    """The smoke variant with packed weights: its router and shared
    experts through ``codr_matmul``, its expert stacks through
    ``codr_gmm``."""
    cfg = smoke_variant(get_config(LITE))
    params = get_model(cfg).init_params(jax.random.PRNGKey(3), cfg)
    cp = compile_params(params, EncodeConfig(n_unique=16), min_size=256,
                        accounting=False)
    return cfg, params, cp


def test_param_count_matches_published():
    """15.71 B parameters (DeepSeek-V2-Lite's model card: 15.7 B)."""
    n = get_config(LITE).param_count()
    assert abs(n - 15.71e9) / 15.71e9 < 0.005, n


def test_smoke_variant_keeps_plain_query_and_yarn():
    cfg = smoke_variant(get_config(LITE))
    assert cfg.q_lora_rank == 0 and cfg.rope_scaling is not None
    assert cfg.moe_gating == "softmax_topk"
    p = get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)
    mixer = p["stack"]["b0"]["mixer"]
    assert "q_proj" in mixer and "q_a_proj" not in mixer
    assert mixer["q_proj"].shape[-1] == cfg.n_heads * (
        cfg.nope_head_dim + cfg.rope_head_dim)
    big = smoke_variant(get_config("deepseek-v2-236b"))
    assert big.q_lora_rank == 32
    assert "q_a_proj" in get_model(big).init_params(
        jax.random.PRNGKey(0), big)["stack"]["b0"]["mixer"]


def test_yarn_matches_closed_form():
    """d = 64, base 1e4, factor 40: the correction dims are 10 and 23;
    between them the ramp blends base**(-2i/d) with it over 40; the
    softmax scale is 192**-0.5 * (0.1 * 0.707 * ln 40 + 1)**2."""
    s = get_config(LITE).rope_scaling
    i = np.arange(32)
    extra = 1e4 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(rope_freqs(64, 1e4, s), want, rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m * m == pytest.approx(1.58963, abs=5e-5)
    assert softmax_scale(192, s) == pytest.approx(192 ** -0.5 * m * m,
                                                  rel=1e-12)
    assert softmax_scale(192, None) == 1.0 / math.sqrt(192)
    np.testing.assert_allclose(rope_freqs(64, 1e4), extra, rtol=1e-12)


@pytest.mark.parametrize("gating", ["topk_softmax", "softmax_topk"])
def test_route_gating_by_hand(gating):
    logits = np.array([[2.0, -1.0, 0.5, 1.5, 0.0],
                       [0.1, 0.3, -2.0, 0.2, 1.0]], np.float32)
    cfg = dataclasses.replace(get_config(LITE), moe_top_k=2, n_experts=5,
                              moe_gating=gating, moe_routed_scale=2.5)
    gates, idx = moe_mod.route(jnp.asarray(logits), cfg)
    top = np.argsort(-logits, axis=1)[:, :2]
    np.testing.assert_array_equal(np.asarray(idx), top)
    picked = np.take_along_axis(logits, top, axis=1)
    if gating == "topk_softmax":      # softmax over the k picked logits
        want = np.exp(picked) / np.exp(picked).sum(1, keepdims=True)
    else:                             # softmax over all, not renormalised
        want = np.exp(picked) / np.exp(logits).sum(1, keepdims=True) * 2.5
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-6)


def test_granite_keeps_mixtral_gating(key):
    """Granite (and Jamba) route as before: the top k logits, a softmax
    over them, the output their weighted experts."""
    cfg = smoke_variant(get_config("granite-moe-1b-a400m"))
    assert cfg.moe_gating == "topk_softmax"
    assert get_config("jamba-v0.1-52b").moe_gating == "topk_softmax"
    p = moe_mod.moe_init(key, cfg)
    x = jax.random.normal(key, (1, 6, cfg.d_model), jnp.float32)
    out, touched = moe_mod.moe_forward(p, x, cfg)
    x2 = x.reshape(-1, cfg.d_model)
    g, idx = jax.lax.top_k(x2 @ p["router"], cfg.moe_top_k)
    g = jax.nn.softmax(g, axis=-1)
    want = jnp.zeros_like(x2)
    for t in range(x2.shape[0]):
        for j in range(cfg.moe_top_k):
            e = int(idx[t, j])
            h = jax.nn.silu(x2[t] @ p["w_experts_gate"][e]) \
                * (x2[t] @ p["w_experts_in"][e])
            want = want.at[t].add(g[t, j] * (h @ p["w_experts_out"][e]))
    np.testing.assert_allclose(np.asarray(out).reshape(x2.shape),
                               np.asarray(want), rtol=2e-4, atol=2e-5)
    assert int(touched) == len(np.unique(np.asarray(idx)))


def test_packed_experts_match_decoded_stack(lite):
    """Serving the packed stacks through ``codr_gmm`` gives the logits
    that decoding every stack first (``tiled``: ``ragged_dot``) gives.
    Tolerance: the ``tiled`` lane rounds the decoded weights and its
    rows to bf16 for ``ragged_dot`` where ``codr_gmm`` keeps them f32,
    so the bf16 logits (2**-8 apart near 0.5, here 0.0078 apart at
    most) may differ by a few units in their last place."""
    cfg, params, cp = lite
    tiled = compile_params(params, EncodeConfig(n_unique=16),
                           backend="tiled", min_size=256, accounting=False)
    api = get_model(cfg)
    tok = jnp.asarray(np.arange(1, 12, dtype=np.int32)[None])
    a, _, na = api.prefill(cp.params, {"tokens": tok}, cfg,
                           count_experts=True)
    b, _, nb = api.prefill(tiled.params, {"tokens": tok}, cfg,
                           count_experts=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=0.02)
    assert int(na) == int(nb) > 0


def test_moe_decode_step_returns_a_cache_like_its_own(lite):
    """A MoE model's decode step hands back a cache of the structure it
    was given, so a caller that feeds it back in (``launch/serve.py``)
    traces its step once; the expert count comes only when asked for."""
    cfg, _, cp = lite
    api = get_model(cfg)
    cache = api.init_cache(cfg, 1, 16)
    tok, pos = jnp.ones((1,), jnp.int32), jnp.int32(0)
    out = api.decode_step(cp.params, cache, tok, pos, cfg)
    assert len(out) == 2
    assert jax.tree.structure(out[1]) == jax.tree.structure(cache)
    _, counted, n = api.decode_step(cp.params, cache, tok, pos, cfg,
                                    count_experts=True)
    assert jax.tree.structure(counted) == jax.tree.structure(cache)
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert n_moe * cfg.moe_top_k <= int(n) <= n_moe * cfg.n_experts


def _decode_text(cfg, params, n_slots=2, max_len=32):
    cb = ContinuousBatcher(params, cfg, n_slots=n_slots, max_len=max_len,
                           kv_dtype="int8")
    return cb.lower_decode_step().as_text()


def test_decode_step_holds_no_dense_expert_stack(lite):
    """The served decode step keeps the expert stacks packed: no
    ``(E, K, N)`` float array in its program, where the decode-then-
    ``ragged_dot`` lane builds them."""
    cfg, params, cp = lite
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    stack = re.compile(rf"tensor<{e}x({d}x{f}|{f}x{d})x(f32|bf16)>")
    assert not stack.search(_decode_text(cfg, cp))
    tiled = compile_params(params, EncodeConfig(n_unique=16),
                           backend="tiled", min_size=256, accounting=False)
    assert stack.search(_decode_text(cfg, tiled))


def test_batcher_records_experts_touched(lite):
    """A MoE model's prefill and decode step carry ``experts_touched``
    on their spans and in the batcher's bounded record; a dense model's
    carry nothing."""
    cfg, _, cp = lite
    n_moe = cfg.n_layers - cfg.n_dense_layers
    cb = ContinuousBatcher(cp, cfg, n_slots=2, max_len=32, kv_dtype="int8")
    tracing.enable()
    try:
        cb.submit(np.arange(1, 7, dtype=np.int32),
                  max_new_tokens=3).result(timeout=600)
        cb.stop_async()
        spans = [s for s in tracing.drain()
                 if s.name in ("batcher.prefill", "batcher.step")]
    finally:
        tracing.disable()
    assert {s.name for s in spans} == {"batcher.prefill", "batcher.step"}
    for s in spans:
        assert 1 <= s.attrs["experts_touched"] <= n_moe * cfg.n_experts
    log = list(cb.experts_touched)
    assert [r[1] for r in log] == ["prefill", "step", "step"]
    assert [r[2] for r in log] == [6, 2, 2]
    assert [r[3] for r in log] == [s.attrs["experts_touched"]
                                   for s in spans]

    qcfg = smoke_variant(get_config("qwen2.5-3b"))
    qp = get_model(qcfg).init_params(jax.random.PRNGKey(0), qcfg)
    qb = ContinuousBatcher(qp, qcfg, n_slots=2, max_len=32)
    tracing.enable()
    try:
        qb.submit(np.arange(1, 5, dtype=np.int32),
                  max_new_tokens=2).result(timeout=600)
        qb.stop_async()
        spans = [s for s in tracing.drain()
                 if s.name in ("batcher.prefill", "batcher.step")]
    finally:
        tracing.disable()
    assert spans and all("experts_touched" not in s.attrs for s in spans)
    assert not qb.experts_touched
