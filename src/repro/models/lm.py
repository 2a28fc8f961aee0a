"""Generic decoder-only language model with heterogeneous layers.

Layers are organized in *periods*: one period = ``cfg.block_pattern``
(e.g. Jamba's ``(m, m, m, attn, m, m, m, m)``), scanned ``n_periods``
times with stacked parameters — the HLO contains one period body
regardless of depth.  Optional non-scanned prologue layers cover
DeepSeek's leading dense layer.  The same forward serves train (causal,
no cache), prefill (returns the KV/state caches) and decode (single
token against preallocated caches).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.common import (DEFAULT_DTYPE, constrain_tokens, embed_init,
                                 embedding_lookup, norm_apply, norm_init,
                                 softmax_xent, unembed)

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_mixer(key, kind: str, cfg):
    if kind == "attn":
        return attn.mla_init(key, cfg) if cfg.use_mla else attn.gqa_init(key, cfg)
    if kind == "mamba":
        return ssm.mamba_init(key, cfg)
    if kind == "mlstm":
        return ssm.mlstm_init(key, cfg)
    if kind == "slstm":
        return ssm.slstm_init(key, cfg)
    raise ValueError(kind)


def _init_layer(key, spec, cfg) -> dict:
    kind, ffn = spec
    k1, k2 = jax.random.split(key)
    p = {"norm1": norm_init(cfg.d_model, cfg.norm_type),
         "mixer": _init_mixer(k1, kind, cfg)}
    if ffn == "dense":
        p["norm2"] = norm_init(cfg.d_model, cfg.norm_type)
        p["mlp"] = moe_mod.mlp_init(k2, cfg.d_model, cfg.d_ff)
    elif ffn == "moe":
        p["norm2"] = norm_init(cfg.d_model, cfg.norm_type)
        p["mlp"] = moe_mod.moe_init(k2, cfg)
    return p


def _init_period(key, cfg) -> dict:
    plan = cfg.layer_plan()
    keys = jax.random.split(key, len(plan))
    return {f"b{i}": _init_layer(keys[i], spec, cfg)
            for i, spec in enumerate(plan)}


def init_params(key, cfg) -> dict:
    ks = jax.random.split(key, 5)
    params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model),
        "final_norm": norm_init(cfg.d_model, cfg.norm_type),
        "stack": jax.vmap(lambda k: _init_period(k, cfg))(
            jax.random.split(ks[1], cfg.n_periods)),
    }
    if not cfg.tied_embeddings:
        params["out_embed"] = embed_init(ks[2], cfg.vocab_size, cfg.d_model)
    if cfg.n_dense_layers:
        pkeys = jax.random.split(ks[3], cfg.n_dense_layers)
        params["prologue"] = [
            _init_layer(pkeys[i], ("attn", "dense"), cfg)
            for i in range(cfg.n_dense_layers)]
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _mixer_cache(kind: str, cfg, batch: int, seq: int, dtype, paged=None):
    if kind == "attn":
        if paged is not None:
            if cfg.use_mla:
                return attn.mla_cache_init_paged(cfg, paged, dtype)
            return attn.gqa_cache_init_paged(cfg, paged, dtype)
        if cfg.use_mla:
            return attn.mla_cache_init(cfg, batch, seq, dtype)
        return attn.gqa_cache_init(cfg, batch, seq, dtype)
    if paged is not None:
        raise NotImplementedError(
            f"paged KV cache covers attention mixers only, got {kind!r} "
            f"({cfg.name}) — SSM states have no sequence axis to page")
    if kind == "mamba":
        return ssm.mamba_state_init(cfg, batch, dtype)
    if kind == "mlstm":
        return ssm.mlstm_state_init(cfg, batch)
    if kind == "slstm":
        return ssm.slstm_state_init(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg, batch: int, seq: int, dtype=DEFAULT_DTYPE,
               paged=None) -> dict:
    """``paged`` is an optional :class:`repro.models.cache.PagedSpec`;
    when given, attention KV leaves become :class:`PagedKV` pools
    (``batch`` must equal ``paged.n_slots``, ``seq`` its ``max_len``)."""
    if paged is not None and (batch != paged.n_slots
                              or seq != paged.max_len):
        raise ValueError(
            f"paged cache geometry mismatch: batch={batch}/seq={seq} vs "
            f"spec n_slots={paged.n_slots}/max_len={paged.max_len}")
    plan = cfg.layer_plan()
    period = {f"b{i}": _mixer_cache(spec[0], cfg, batch, seq, dtype, paged)
              for i, spec in enumerate(plan)}
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape),
        period)
    out = {"stack": stacked}
    if cfg.n_dense_layers:
        out["prologue"] = [
            _mixer_cache("attn", cfg, batch, seq, dtype, paged)
            for _ in range(cfg.n_dense_layers)]
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block_apply(lp, x, spec, cfg, mode, cache, pos, positions):
    """One layer → ``(x, new_cache, experts touched)``; the count is
    None for a layer without experts."""
    kind, _ffn = spec
    h = norm_apply(x, lp["norm1"], cfg.norm_type, f32=cfg.norm_f32)
    if kind == "attn":
        mixer = lp["mixer"]
        if mode == "decode":
            fn = attn.mla_decode if cfg.use_mla else attn.gqa_decode
            out, new_cache = fn(mixer, h, cfg, cache, pos)
        else:
            fn = attn.mla_forward if cfg.use_mla else attn.gqa_forward
            out, new_cache = fn(mixer, h, cfg, positions)
    elif kind == "mamba":
        if mode == "decode":
            out, new_cache = ssm.mamba_decode(lp["mixer"], h, cfg, cache)
        else:
            out, new_cache = ssm.mamba_forward(lp["mixer"], h, cfg,
                                               chunk=cfg.mamba_chunk)
    elif kind == "mlstm":
        out, new_cache = ssm.mlstm_forward(
            lp["mixer"], h, cfg, state=cache if mode == "decode" else None)
    elif kind == "slstm":
        out, new_cache = ssm.slstm_forward(
            lp["mixer"], h, cfg, state=cache if mode == "decode" else None)
    else:
        raise ValueError(kind)
    x = x + out
    touched = None
    if "mlp" in lp:
        h = norm_apply(x, lp["norm2"], cfg.norm_type, f32=cfg.norm_f32)
        if "router" in lp["mlp"]:
            out, touched = moe_mod.moe_forward(lp["mlp"], h, cfg, mode=mode)
        else:
            out = moe_mod.mlp_forward(lp["mlp"], h, cfg.act)
        x = x + out
    x = constrain_tokens(x)
    return x, new_cache, touched


def has_experts(cfg) -> bool:
    """Whether any layer of ``cfg`` routes to experts."""
    return any(f == "moe" for _, f in cfg.layer_plan())


def _add(total, touched):
    """A running expert count (None: not counting) plus a layer's."""
    return total if total is None or touched is None else total + touched


def forward(params, tokens, cfg, *, mode: str = "train", cache=None,
            pos=None, prefix=None, count_experts: bool = False):
    """tokens (B, S) int32 → (logits, new_cache).

    mode='train'  : causal forward, logits for every position, no cache.
    mode='prefill': causal forward, logits for the LAST position, cache out.
    mode='decode' : S == 1, attends into the preallocated cache at ``pos``.

    With ``count_experts`` it returns ``(logits, new_cache, touched)``:
    the (layer, expert) pairs that received rows in this call, an int32,
    or None where nothing is counted (no experts, training, or the mesh
    lanes: ``moe.counts_experts``).
    """
    plan = cfg.layer_plan()
    counting = (count_experts and mode != "train" and has_experts(cfg)
                and moe_mod.counts_experts(cfg))
    x = embedding_lookup(params["embed"], tokens, DEFAULT_DTYPE)
    if prefix is not None:
        x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
    x = constrain_tokens(x)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    new_prologue = []
    for i, lp in enumerate(params.get("prologue", [])):
        c = cache["prologue"][i] if cache else None
        x, nc, _ = _block_apply(lp, x, ("attn", "dense"), cfg, mode, c, pos,
                                positions)
        new_prologue.append(nc)

    touched = jnp.int32(0) if counting else None
    if mode == "train":
        def body(xc, period_params):
            for i, spec in enumerate(plan):
                xc, _, _ = _block_apply(period_params[f"b{i}"], xc, spec,
                                        cfg, mode, None, pos, positions)
            return xc, None
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["stack"])
        stack_cache = None
    elif mode == "prefill":
        def body(carry, period_params):
            xc, n = carry if counting else (carry, None)
            caches = {}
            for i, spec in enumerate(plan):
                xc, nc, t = _block_apply(period_params[f"b{i}"], xc, spec,
                                         cfg, mode, None, pos, positions)
                caches[f"b{i}"] = nc
                n = _add(n, t)
            return ((xc, n) if counting else xc), caches
        carry, stack_cache = jax.lax.scan(
            body, (x, touched) if counting else x, params["stack"])
        x, touched = carry if counting else (carry, None)
    else:  # decode
        # the cache rides in the scan CARRY with per-period in-place index
        # updates (donation-friendly; scan-ys stacking round-trips the
        # whole cache through a staging buffer on some backends)
        def body(carry, xs):
            xc, cache_stack, *n = carry
            n = n[0] if counting else None
            period_params, idx = xs
            period_cache = jax.tree.map(
                lambda buf: jax.lax.dynamic_index_in_dim(
                    buf, idx, 0, keepdims=False), cache_stack)
            new_caches = {}
            for i, spec in enumerate(plan):
                xc, nc, t = _block_apply(period_params[f"b{i}"], xc, spec,
                                         cfg, mode, period_cache[f"b{i}"],
                                         pos, positions)
                new_caches[f"b{i}"] = nc
                n = _add(n, t)
            cache_stack = jax.tree.map(
                lambda buf, nc: jax.lax.dynamic_update_index_in_dim(
                    buf, nc.astype(buf.dtype), idx, 0),
                cache_stack, new_caches)
            return (xc, cache_stack) + ((n,) if counting else ()), None

        carry, _ = jax.lax.scan(
            body, (x, cache["stack"]) + ((touched,) if counting else ()),
            (params["stack"], jnp.arange(cfg.n_periods)))
        x, stack_cache = carry[:2]
        touched = carry[2] if counting else None

    x = norm_apply(x, params["final_norm"], cfg.norm_type,
                   f32=cfg.norm_f32)
    if mode == "prefill":
        x = x[:, -1:]
    out_embed = params.get("out_embed", params["embed"])
    logits = unembed(x, out_embed)

    new_cache = None
    if mode != "train":
        new_cache = {"stack": stack_cache}
        if cfg.n_dense_layers:
            new_cache["prologue"] = new_prologue
    if count_experts:
        return logits, new_cache, touched
    return logits, new_cache


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def train_loss(params, batch, cfg):
    logits, _ = forward(params, batch["tokens"], cfg, mode="train",
                        prefix=batch.get("prefix"))
    if cfg.frontend_seq and "prefix" in batch:
        logits = logits[:, cfg.frontend_seq:]
    mask = batch.get("mask")
    return softmax_xent(logits[:, :-1], batch["tokens"][:, 1:],
                        mask[:, 1:] if mask is not None else None)


def prefill(params, tokens, cfg, prefix=None, count_experts: bool = False):
    return forward(params, tokens, cfg, mode="prefill", prefix=prefix,
                   count_experts=count_experts)


def decode_step(params, cache, token, pos, cfg, count_experts: bool = False):
    """token (B,) int32, pos scalar int32 → (logits (B, V), cache), and
    with ``count_experts`` the experts given rows (``forward``)."""
    logits, *rest = forward(params, token[:, None], cfg, mode="decode",
                            cache=cache, pos=pos, count_experts=count_experts)
    return (logits[:, 0], *rest)
