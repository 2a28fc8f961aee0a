"""Attention: GQA (bias / qk-norm options), chunked flash-style softmax
attention for long sequences, KV-cache decode, and DeepSeek-V2 MLA with
the absorbed decode form."""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import cache as cache_lib
from repro.models.common import (apply_rope, dense_init, dense_weight,
                                 linear, norm_apply, norm_init, rms_norm,
                                 softmax_scale)
from repro.sharding import current_ctx, maybe_constrain


def _einsum_f32(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """einsum with f32 accumulation.  On TPU (and in the dry-run, which
    targets TPU semantics) keep operands in their storage dtype and set
    preferred_element_type — no upcast copies of the big operand.  The
    CPU *runtime* cannot execute mixed bf16→f32 dots (DotThunk), so the
    executing path upcasts."""
    if jax.default_backend() == "tpu" or os.environ.get("REPRO_DRYRUN"):
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))

# ---------------------------------------------------------------------------
# chunked (flash-style) attention — pure JAX online softmax
# ---------------------------------------------------------------------------

def _pick_chunk(s: int, preferred: int) -> int:
    """Largest divisor of ``s`` that is ≤ preferred."""
    c = min(preferred, s)
    while s % c:
        c -= 1
    return c


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, q_chunk: int = 512, kv_chunk: int = 1024,
                    scale: float | None = None,
                    acc_dtype=jnp.float32) -> jax.Array:
    """q (B,Sq,Hq,Dk), k (B,Skv,Hkv,Dk), v (B,Skv,Hkv,Dv) → (B,Sq,Hq,Dv).

    Online-softmax over kv chunks inside a scan over q chunks: peak live
    score buffer is (B,Hkv,G,qc,kc) instead of (B,H,S,S).  GQA via head
    grouping (no kv repeat materialization).  ``acc_dtype`` is the dtype
    of the materialized score/accumulator buffers — the §Perf lever
    ``attn_f32=False`` uses bf16 (the max-subtracted exponentials keep
    values in [0,1] where bf16 is safe; MXU accumulation stays f32 on
    hardware via preferred_element_type).
    """
    b, sq, hq, dk = q.shape
    _, skv, hkv, dv = v.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    qc = _pick_chunk(sq, q_chunk)
    kc = _pick_chunk(skv, kv_chunk)
    n_q, n_k = sq // qc, skv // kc
    neg = jnp.asarray(-1e30, acc_dtype)   # bf16 exponent range covers this

    qr = q.reshape(b, n_q, qc, hkv, g, dk).transpose(1, 0, 3, 4, 2, 5)
    kr = k.reshape(b, n_k, kc, hkv, dk).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(b, n_k, kc, hkv, dv).transpose(1, 0, 3, 2, 4)

    def q_step(_, qi_blk):
        qi, q_blk = qi_blk                       # q_blk (B,Hkv,G,qc,Dk)

        def kv_step(carry, ki_blk):
            m, l, acc = carry
            ki, k_blk, v_blk = ki_blk
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                           preferred_element_type=acc_dtype) * scale
            if causal:
                qpos = qi * qc + jnp.arange(qc)
                kpos = ki * kc + jnp.arange(kc)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None, None], s, neg)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, v_blk.astype(acc_dtype),
                preferred_element_type=acc_dtype)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, hkv, g, qc), neg, acc_dtype),
                jnp.zeros((b, hkv, g, qc), acc_dtype),
                jnp.zeros((b, hkv, g, qc, dv), acc_dtype))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(n_k), kr, vr))
        out = acc / jnp.maximum(l, 1e-8).astype(acc_dtype)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(n_q), qr))
    # outs (n_q, B, Hkv, G, qc, Dv) → (B, Sq, Hq, Dv)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, hq, dv)


def decode_positions(pos, b: int) -> jax.Array:
    """Decode-step position operand → the ``(B, 1)`` int32 matrix RoPE
    consumes.  ``pos`` is either a scalar (every row writes the same
    position — the classic single-request batch) or per-row ``(B,)``
    (a continuous-batching slot pool where each row sits at its own
    sequence position, docs/DESIGN.md §3.4)."""
    p = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(p[:, None] if p.ndim else p, (b, 1))


def cache_update(cache: jax.Array, new: jax.Array, pos) -> jax.Array:
    """Write the single-token block ``new`` (B, 1, ...) into the
    (B, S, ...) ``cache`` at ``pos`` (scalar or per-row ``(B,)``).  The
    scalar form keeps the contiguous ``dynamic_update_slice``; the
    per-row form lowers to a batched one-row scatter — the slot-pool
    cache-slicing primitive."""
    new = new.astype(cache.dtype)
    p = jnp.asarray(pos)
    if p.ndim == 0:
        start = (0, p) + (0,) * (cache.ndim - 2)
        return jax.lax.dynamic_update_slice(cache, new, start)
    return cache.at[jnp.arange(cache.shape[0]), p].set(new[:, 0])


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, scale: float | None = None
                     ) -> jax.Array:
    """Single-token attention against a (B,S,Hkv,D) cache, masked to
    positions ≤ pos (pos may be per-batch (B,) or scalar)."""
    b, sq, hq, dk = q.shape
    _, s, hkv, dv = v_cache.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    qg = q.reshape(b, sq, hkv, g, dk)
    scores = _einsum_f32("bqhgd,bshd->bhgqs", qg,
                         k_cache.astype(qg.dtype)) * scale
    idx = jnp.arange(s)
    posb = jnp.broadcast_to(jnp.asarray(pos), (b,))
    mask = idx[None, :] <= posb[:, None]                        # (B, S)
    scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = _einsum_f32("bhgqs,bshd->bqhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, sq, hq, dv).astype(q.dtype)


def decode_attention_dist(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, k_new: jax.Array,
                          v_new: jax.Array, pos: jax.Array, *,
                          scale: float | None = None):
    """Sequence-parallel decode attention with in-shard cache update
    (§Perf optimization).

    The cache stays sharded over ``model`` on its sequence axis — both
    the position-``pos`` update (only the owning shard writes; a plain
    XLA dynamic-update-slice on a sequence-sharded cache triggers
    GSPMD's involuntary full rematerialization, i.e. a cache gather)
    and the attention (each shard computes a local flash-style partial
    softmax; three tiny psums combine max / denominator / accumulator).
    The cache is NEVER gathered.  Returns (out, k_cache, v_cache).
    Falls back to the naive path without a mesh or when S doesn't
    divide."""
    ctx = current_ctx()
    b, sq, hq, dk = q.shape
    _, s, hkv, dv = v_cache.shape
    if (ctx is None or ctx.axis_size("model") <= 1
            or s % ctx.axis_size("model")):
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new.astype(v_cache.dtype), (0, pos, 0, 0))
        return (decode_attention(q, k_cache, v_cache, pos, scale=scale),
                k_cache, v_cache)
    mesh = ctx.mesh
    msize = ctx.axis_size("model")
    s_loc = s // msize
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    g = hq // hkv
    bspec = ctx.batch_spec
    if bspec is not None:
        baxes = bspec if isinstance(bspec, tuple) else (bspec,)
        btotal = 1
        for a in baxes:
            btotal *= ctx.axis_size(a)
        if b % btotal:
            bspec = None

    def body(q_l, k_l, v_l, kn, vn, pos_l):
        shard = jax.lax.axis_index("model")
        # -- in-shard cache update: write-or-keep at the clamped slot ----
        local = pos_l - shard * s_loc
        in_range = (local >= 0) & (local < s_loc)
        slot = jnp.clip(local, 0, s_loc - 1)
        old_k = jax.lax.dynamic_slice(
            k_l, (0, slot, 0, 0), (k_l.shape[0], 1, hkv, dk))
        old_v = jax.lax.dynamic_slice(
            v_l, (0, slot, 0, 0), (v_l.shape[0], 1, hkv, dv))
        k_l = jax.lax.dynamic_update_slice(
            k_l, jnp.where(in_range, kn.astype(k_l.dtype), old_k),
            (0, slot, 0, 0))
        v_l = jax.lax.dynamic_update_slice(
            v_l, jnp.where(in_range, vn.astype(v_l.dtype), old_v),
            (0, slot, 0, 0))
        # -- local partial softmax + global combine ----------------------
        qg = q_l.reshape(q_l.shape[0], sq, hkv, g, dk)
        sc = _einsum_f32("bqhgd,bshd->bhgqs", qg, k_l) * scale
        idx = shard * s_loc + jnp.arange(s_loc)
        posb = jnp.broadcast_to(jnp.asarray(pos_l), (q_l.shape[0],))
        mask = idx[None, :] <= posb[:, None]
        sc = jnp.where(mask[:, None, None, None, :], sc, -1e30)
        m_l = sc.max(axis=-1)
        p = jnp.exp(sc - m_l[..., None])
        l_l = p.sum(axis=-1)
        acc_l = _einsum_f32("bhgqs,bshd->bhgqd", p.astype(v_l.dtype), v_l)
        m_g = jax.lax.pmax(m_l, "model")
        corr = jnp.exp(m_l - m_g)
        l_g = jax.lax.psum(l_l * corr, "model")
        acc_g = jax.lax.psum(acc_l * corr[..., None], "model")
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        # (B,Hkv,G,q,Dv) → (B,q,Hq,Dv)
        out = out.transpose(0, 3, 1, 2, 4).reshape(
            q_l.shape[0], sq, hq, dv).astype(q_l.dtype)
        return out, k_l, v_l

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, "model", None, None),
                  P(bspec, "model", None, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None), P()),
        out_specs=(P(bspec, None, None, None),
                   P(bspec, "model", None, None),
                   P(bspec, "model", None, None)),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, jnp.asarray(pos, jnp.int32))


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def gqa_init(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "q_proj": dense_init(ks[0], d, hq * hd),
        "k_proj": dense_init(ks[1], d, hkv * hd),
        "v_proj": dense_init(ks[2], d, hkv * hd),
        "o_proj": dense_init(ks[3], hq * hd, d),
    }
    if cfg.qkv_bias:
        p["q_bias"] = jnp.zeros((hq * hd,), jnp.float32)
        p["k_bias"] = jnp.zeros((hkv * hd,), jnp.float32)
        p["v_bias"] = jnp.zeros((hkv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd, "rmsnorm")
        p["k_norm"] = norm_init(hd, "rmsnorm")
    return p


def _qkv(p, x, cfg, positions):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(x, p["q_proj"], p.get("q_bias")).reshape(b, s, hq, hd)
    k = linear(x, p["k_proj"], p.get("k_bias")).reshape(b, s, hkv, hd)
    v = linear(x, p["v_proj"], p.get("v_bias")).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["w"])
        k = rms_norm(k, p["k_norm"]["w"])
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    if hq % 16 == 0:  # hint only when cleanly divisible by any model axis
        q = maybe_constrain(q, "batch", None, "model", None)
    return q, k, v


def gqa_forward(p, x, cfg, positions, *, causal=True):
    """Full-sequence GQA (train / prefill). Returns (out, (k, v))."""
    q, k, v = _qkv(p, x, cfg, positions)
    out = flash_attention(q, k, v, causal=causal,
                          q_chunk=cfg.attn_q_chunk,
                          kv_chunk=cfg.attn_kv_chunk,
                          acc_dtype=jnp.float32 if cfg.attn_f32
                          else jnp.bfloat16)
    b, s = x.shape[:2]
    out = linear(out.reshape(b, s, -1), p["o_proj"])
    return out, (k, v)


def gqa_decode(p, x, cfg, cache, pos):
    """Single-token decode. cache = (k, v) each (B, S, Hkv, hd);
    pos is the position being written — scalar int32, or per-row (B,)
    int32 when the batch is a continuous-batching slot pool whose rows
    sit at different sequence positions (docs/DESIGN.md §3.4).  The
    sequence-parallel ``dist`` lane needs a uniform write position, so
    per-row pos always takes the standard lane."""
    k_cache, v_cache = cache
    positions = decode_positions(pos, x.shape[0])
    q, k_new, v_new = _qkv(p, x, cfg, positions)
    if isinstance(k_cache, cache_lib.PagedKV):
        # paged lane: write the new row into the slot's page, then run
        # the standard masked attention over the gathered dense view —
        # bf16 pages reproduce the contiguous cache byte-for-byte
        k_cache = k_cache.update(k_new, pos)
        v_cache = v_cache.update(v_new, pos)
        out = decode_attention(q, k_cache.gather(), v_cache.gather(), pos)
    elif cfg.decode_attn == "dist" and jnp.ndim(pos) == 0:
        out, k_cache, v_cache = decode_attention_dist(
            q, k_cache, v_cache, k_new, v_new, pos)
    else:
        k_cache = cache_update(k_cache, k_new, pos)
        v_cache = cache_update(v_cache, v_new, pos)
        out = decode_attention(q, k_cache, v_cache, pos)
    b = x.shape[0]
    out = linear(out.reshape(b, 1, -1), p["o_proj"])
    return out, (k_cache, v_cache)


def gqa_cache_init(cfg, batch: int, seq: int, dtype=jnp.bfloat16):
    shape = (batch, seq, cfg.n_kv_heads, cfg.head_dim)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def gqa_cache_init_paged(cfg, spec, dtype=jnp.bfloat16):
    feat = (cfg.n_kv_heads, cfg.head_dim)
    return (cache_lib.paged_kv_init(spec, feat, dtype),
            cache_lib.paged_kv_init(spec, feat, dtype))


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2) — compressed KV cache
# ---------------------------------------------------------------------------

def mla_init(key, cfg) -> dict:
    """Without q-LoRA (``q_lora_rank`` 0, DeepSeek-V2-Lite) the query
    is one ``q_proj``; with it, ``q_a_proj`` → RMSNorm → ``q_b_proj``."""
    ks = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    if qr:
        q = {"q_a_proj": dense_init(ks[0], d, qr),
             "q_a_norm": norm_init(qr, "rmsnorm"),
             "q_b_proj": dense_init(ks[1], qr, h * (dn + dr))}
    else:
        q = {"q_proj": dense_init(ks[0], d, h * (dn + dr))}
    return {
        **q,
        "kv_a_proj": dense_init(ks[2], d, kr + dr),
        "kv_a_norm": norm_init(kr, "rmsnorm"),
        "kv_b_proj": dense_init(ks[3], kr, h * (dn + dv)),
        "o_proj": dense_init(ks[4], h * dv, d),
    }


def _mla_q(p, x, cfg, positions):
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    if "q_proj" in p:
        q = linear(x, p["q_proj"])
    else:
        qa = norm_apply(linear(x, p["q_a_proj"]), p["q_a_norm"], "rmsnorm")
        q = linear(qa, p["q_b_proj"])
    q = q.reshape(b, s, h, dn + dr)
    qn, qrot = q[..., :dn], q[..., dn:]
    qrot = apply_rope(qrot, positions, cfg.rope_theta, cfg.rope_scaling)
    return qn, qrot


def _mla_ckv(p, x, cfg, positions):
    kr, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    kv_a = linear(x, p["kv_a_proj"])
    ckv = norm_apply(kv_a[..., :kr], p["kv_a_norm"], "rmsnorm")
    krot = kv_a[..., kr:][:, :, None, :]                 # (B,S,1,dr)
    krot = apply_rope(krot, positions, cfg.rope_theta,
                      cfg.rope_scaling)[:, :, 0]
    return ckv, krot


def mla_forward(p, x, cfg, positions, *, causal=True):
    """Materialized form (train / prefill). Returns (out, (ckv, krot))."""
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    qn, qrot = _mla_q(p, x, cfg, positions)
    ckv, krot = _mla_ckv(p, x, cfg, positions)
    kv = linear(ckv, p["kv_b_proj"]).reshape(b, s, h, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([kn, jnp.broadcast_to(krot[:, :, None, :],
                                              (b, s, h, dr)).astype(kn.dtype)],
                        axis=-1)
    q = jnp.concatenate([qn, qrot], axis=-1)
    out = flash_attention(q, k, v, causal=causal,
                          q_chunk=cfg.attn_q_chunk,
                          kv_chunk=cfg.attn_kv_chunk,
                          scale=softmax_scale(dn + dr, cfg.rope_scaling),
                          acc_dtype=jnp.float32 if cfg.attn_f32
                          else jnp.bfloat16)
    out = linear(out.reshape(b, s, -1), p["o_proj"])
    return out, (ckv, krot)


def mla_decode(p, x, cfg, cache, pos):
    """Absorbed decode: attention runs in the kv_lora latent space —
    cache is (ckv (B,S,c), krot (B,S,dr)); per-token HBM traffic is
    c + dr per position instead of H*(dn+dv)."""
    b = x.shape[0]
    h = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    c = cfg.kv_lora_rank
    ckv_cache, krot_cache = cache
    positions = decode_positions(pos, b)
    qn, qrot = _mla_q(p, x, cfg, positions)              # (B,1,H,dn/dr)
    ckv_new, krot_new = _mla_ckv(p, x, cfg, positions)
    if isinstance(ckv_cache, cache_lib.PagedKV):
        ckv_cache = ckv_cache.update(ckv_new, pos)
        krot_cache = krot_cache.update(krot_new, pos)
        ckv_dense, krot_dense = ckv_cache.gather(), krot_cache.gather()
    else:
        ckv_cache = cache_update(ckv_cache, ckv_new, pos)
        krot_cache = cache_update(krot_cache, krot_new, pos)
        ckv_dense, krot_dense = ckv_cache, krot_cache

    # absorbed form consumes the raw weight, not a matmul — decode a
    # packed leaf on dispatch (identity for dense params)
    w_kv_b = dense_weight(p["kv_b_proj"]).reshape(c, h, dn + dv)
    w_uk, w_uv = w_kv_b[..., :dn], w_kv_b[..., dn:]
    q_lat = _einsum_f32("bqhd,chd->bqhc", qn, w_uk.astype(qn.dtype))
    scores = (_einsum_f32("bqhc,bsc->bhqs", q_lat.astype(ckv_dense.dtype),
                          ckv_dense)
              + _einsum_f32("bqhd,bsd->bhqs", qrot.astype(krot_dense.dtype),
                            krot_dense))
    scores = scores * softmax_scale(dn + dr, cfg.rope_scaling)
    posb = jnp.broadcast_to(jnp.asarray(pos), (b,))
    mask = jnp.arange(ckv_dense.shape[1])[None, :] <= posb[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1)
    out_lat = _einsum_f32("bhqs,bsc->bqhc", attn.astype(ckv_dense.dtype),
                          ckv_dense)
    out = jnp.einsum("bqhc,chd->bqhd", out_lat, w_uv.astype(jnp.float32))
    out = linear(out.reshape(b, 1, h * dv).astype(x.dtype), p["o_proj"])
    return out, (ckv_cache, krot_cache)


def mla_cache_init(cfg, batch: int, seq: int, dtype=jnp.bfloat16):
    return (jnp.zeros((batch, seq, cfg.kv_lora_rank), dtype),
            jnp.zeros((batch, seq, cfg.rope_head_dim), dtype))


def mla_cache_init_paged(cfg, spec, dtype=jnp.bfloat16):
    return (cache_lib.paged_kv_init(spec, (cfg.kv_lora_rank,), dtype),
            cache_lib.paged_kv_init(spec, (cfg.rope_head_dim,), dtype))
