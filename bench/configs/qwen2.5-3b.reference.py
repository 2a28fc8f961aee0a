"""Plain float32 reference of a dense GQA decoder (Qwen2 layout: RMSNorm,
rotary embedding over the whole head, QKV bias, SwiGLU MLP, tied
unembedding), written from the published description in ``jax.numpy``
at "highest" matmul precision.  It imports nothing of the system under
test: the weights are rebuilt from the seed by ``bench.lib.lm_weights``,
one layer at a time, so that it fits beside its activations.

``fp8=True`` is the control: every matmul operand, the attention
scores' and values' too, rounded to float8 e4m3 (per-tensor scale for
weights, per-row for activations), the step below the bfloat16
activations the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import lm_weights as lw

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
PROJ = {  # name -> (leaf path in the served tree, in-features key)
    "q_proj": "stack/b0/mixer/q_proj", "k_proj": "stack/b0/mixer/k_proj",
    "v_proj": "stack/b0/mixer/v_proj", "o_proj": "stack/b0/mixer/o_proj",
    "gate_proj": "stack/b0/mlp/gate_proj", "up_proj": "stack/b0/mlp/up_proj",
    "down_proj": "stack/b0/mlp/down_proj"}
DENSE = {"q_bias": "stack/b0/mixer/q_bias", "k_bias": "stack/b0/mixer/k_bias",
         "v_bias": "stack/b0/mixer/v_bias", "norm1": "stack/b0/norm1/w",
         "norm2": "stack/b0/norm2/w"}
EMBED, FINAL_NORM = "embed", "final_norm/w"


def shapes(c: dict) -> dict:
    d, hd = c["hidden_size"], c["hidden_size"] // c["num_attention_heads"]
    q, kv, f = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd, \
        c["intermediate_size"]
    return {"q_proj": (d, q), "k_proj": (d, kv), "v_proj": (d, kv),
            "o_proj": (q, d), "gate_proj": (d, f), "up_proj": (d, f),
            "down_proj": (f, d), "q_bias": (q,), "k_bias": (kv,),
            "v_bias": (kv,), "norm1": (d,), "norm2": (d,)}


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8):
    if fp8:
        a, b = _fp8(a, -1), _fp8(b, None)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * w


def _rope(x, theta):
    p, _, hd = x.shape
    freqs = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ang = jnp.arange(p, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("c", "bits"))
def layer_weights(key, layer, *, c, bits):
    """Layer ``layer``'s dense float32 weights, rebuilt from the seed."""
    c = dict(c)
    sh, w = shapes(c), {}
    for name, path in PROJ.items():
        k = lw.leaf_key(key, path)
        kk, n = sh[name]
        tbl = lw.table(k, 1 << bits)
        w[name] = lw.decode(lw.words(k, layer, kk, n, bits), tbl,
                            lw.scale(tbl, 1.0 / math.sqrt(kk)), bits)
    for name, path in DENSE.items():
        w[name] = lw.dense(lw.leaf_key(key, path), layer, sh[name],
                           lw.dense_kind(path))
    return w


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def layer_apply(x, w, *, c, fp8):
    """One decoder layer over a causal sequence ``x (P, d)``."""
    c = dict(c)
    p, hq, hkv = x.shape[0], c["num_attention_heads"], c["num_key_value_heads"]
    hd, g = c["hidden_size"] // hq, hq // hkv
    h = _rms(x, w["norm1"])
    q = (_mm(h, w["q_proj"], fp8) + w["q_bias"]).reshape(p, hq, hd)
    k = (_mm(h, w["k_proj"], fp8) + w["k_bias"]).reshape(p, hkv, hd)
    v = (_mm(h, w["v_proj"], fp8) + w["v_bias"]).reshape(p, hkv, hd)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    qg = q.reshape(p, hkv, g, hd)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) / math.sqrt(hd)
    causal = jnp.arange(p)[:, None] >= jnp.arange(p)[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if fp8:
        a = _fp8(a, -1)
    o = jnp.einsum("hgqk,khd->qhgd", a, v, precision=HI).reshape(p, hq * hd)
    x = x + _mm(o, w["o_proj"], fp8)
    h = _rms(x, w["norm2"])
    m = jax.nn.silu(_mm(h, w["gate_proj"], fp8)) * _mm(h, w["up_proj"], fp8)
    return x + _mm(m, w["down_proj"], fp8)


@functools.partial(jax.jit, static_argnames=("c", "bits"))
def embed_and_norm(key, *, c, bits):
    c = dict(c)
    k = lw.leaf_key(key, EMBED)
    tbl = lw.table(k, 1 << bits)
    emb = lw.decode(lw.words(k, 0, c["vocab_size"], c["hidden_size"], bits),
                    tbl, lw.scale(tbl, lw.EMBED_STD), bits)
    fnorm = lw.dense(lw.leaf_key(key, FINAL_NORM), 0, (c["hidden_size"],),
                     "norm")
    return emb, fnorm


@functools.partial(jax.jit, static_argnames=("fp8",))
def unembed(x, fnorm, emb, *, fp8):
    return _mm(_rms(x, fnorm), emb.T, fp8)


def logits_at(c: dict, seed: int, seqs, positions, *, pad_to: int,
              bits: int, fp8: bool = False,
              bucket: int = 512) -> list[np.ndarray]:
    """For each token sequence ``seqs[i]``, the float32 logits that
    predict the token after each position in ``positions[i]``.  Each
    sequence is padded at its end to a multiple of ``bucket`` (at most
    ``pad_to``); causal attention leaves earlier positions untouched, so
    a few programs serve every length."""
    ch = tuple(sorted((k, v) for k, v in c.items()
                      if isinstance(v, (int, float, str, bool))))
    key = lw.seed_key(seed)
    emb, fnorm = embed_and_norm(key, c=ch, bits=bits)
    xs = []
    for s in seqs:
        tok = np.zeros(min(pad_to, -(-len(s) // bucket) * bucket), np.int32)
        tok[:len(s)] = s
        xs.append(jnp.take(emb, jnp.asarray(tok), axis=0))
    for layer in range(c["num_hidden_layers"]):
        w = layer_weights(key, layer, c=ch, bits=bits)
        xs = [layer_apply(x, w, c=ch, fp8=fp8) for x in xs]
        del w
    out = []
    for x, pos in zip(xs, positions):
        # positions padded to a multiple of 64 (repeating the last), so a
        # few unembedding programs serve every count of served tokens
        n = len(pos)
        padded = np.concatenate([pos, np.full(-n % 64, pos[-1])])
        out.append(np.asarray(unembed(x[jnp.asarray(padded)], fnorm, emb,
                                      fp8=fp8))[:n])
    return out
