"""Per-arch smoke tests: a REDUCED config of every assigned architecture
runs one forward/train step on CPU — output shapes + no NaNs.  The full
configs are exercised only via the dry-run (ShapeDtypeStruct, no
allocation)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_variant
from repro.models import get_model

B, S = 2, 32


def _batch(cfg, key):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.family == "encdec" or cfg.frontend:
        batch["prefix"] = jax.random.normal(key, (B, cfg.frontend_seq,
                                                  cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch, key):
    cfg = smoke_variant(get_config(arch))
    api = get_model(cfg)
    params = api.init_params(key, cfg)
    batch = _batch(cfg, key)
    loss, grads = jax.value_and_grad(
        lambda p: api.train_loss(p, batch, cfg))(params)
    assert np.isfinite(float(loss)), arch
    # rough sanity: ~uniform prediction at init
    assert float(loss) < np.log(cfg.vocab_size) * 2
    leaves = jax.tree.leaves(grads)
    assert leaves and all(np.isfinite(np.asarray(g)).all() for g in leaves)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_serve_step(arch, key):
    cfg = smoke_variant(get_config(arch))
    api = get_model(cfg)
    params = api.init_params(key, cfg)
    batch = _batch(cfg, key)
    logits, cache = api.prefill(params, batch, cfg)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    cache = api.init_cache(cfg, B, S)
    tok = batch["tokens"][:, 0]
    logits, cache = api.decode_step(params, cache, tok, jnp.int32(0), cfg)
    assert logits.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ["qwen3-32b", "deepseek-v2-236b",
                                  "deepseek-v2-lite", "jamba-v0.1-52b",
                                  "xlstm-350m"])
def test_decode_matches_prefill_f32(arch, key):
    """Incremental decode must reproduce the parallel forward exactly
    (f32; bf16 differs only by rounding — verified manually)."""
    import repro.models.common as common
    import repro.models.lm as lm_mod
    old = common.DEFAULT_DTYPE
    common.DEFAULT_DTYPE = jnp.float32
    lm_mod.DEFAULT_DTYPE = jnp.float32
    try:
        cfg = smoke_variant(get_config(arch))
        cfg = dataclasses.replace(cfg, remat=False)
        api = get_model(cfg)
        params = api.init_params(key, cfg)
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        lg_ref, _ = api.prefill(params, {"tokens": tokens}, cfg)
        cache = api.init_cache(cfg, B, S, dtype=jnp.float32)
        lg = None
        for t in range(S):
            lg, cache = api.decode_step(params, cache, tokens[:, t],
                                        jnp.int32(t), cfg)
        rel = (float(jnp.abs(lg - lg_ref[:, 0]).max())
               / max(float(jnp.abs(lg_ref).max()), 1e-6))
        assert rel < 1e-4, (arch, rel)
    finally:
        common.DEFAULT_DTYPE = old
        lm_mod.DEFAULT_DTYPE = old


def test_moe_routing_is_topk(key):
    """Every token's MoE output uses exactly top-k experts: perturbing a
    non-selected expert's weights must not change the output."""
    from repro.models import moe as moe_mod
    cfg = smoke_variant(get_config("granite-moe-1b-a400m"))
    p = moe_mod.moe_init(key, cfg)
    x = jax.random.normal(key, (1, 8, cfg.d_model), jnp.float32)
    out1, _ = moe_mod.moe_forward(p, x, cfg)
    logits = jnp.dot(x.reshape(-1, cfg.d_model),
                     p["router"].astype(jnp.float32))
    _, used = jax.lax.top_k(logits, cfg.moe_top_k)
    unused = [e for e in range(cfg.n_experts)
              if e not in np.unique(np.asarray(used))]
    if unused:
        p2 = dict(p)
        p2["w_experts_in"] = p["w_experts_in"].at[unused[0]].set(123.0)
        out2, _ = moe_mod.moe_forward(p2, x, cfg)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def test_flash_attention_matches_naive(key):
    from repro.models.attention import flash_attention
    b, s, h, d = 2, 64, 4, 16
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, 2, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, 2, d))
    out = flash_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
    # naive reference
    qg = q.reshape(b, s, 2, 2, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    ref = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, -1), v)
    ref = ref.reshape(b, s, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_mamba_chunked_scan_matches_sequential(key):
    from repro.models.ssm import _ssm_scan_chunked
    b, s, d, n = 2, 32, 4, 3
    a = jax.random.uniform(key, (b, s, d, n), minval=0.5, maxval=0.99)
    bb = jax.random.normal(jax.random.fold_in(key, 1), (b, s, d, n))
    h0 = jnp.zeros((b, d, n))
    hs = _ssm_scan_chunked(a, bb, h0, chunk=8)
    # sequential reference
    h = h0
    outs = []
    for t in range(s):
        h = a[:, t] * h + bb[:, t]
        outs.append(h)
    ref = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
