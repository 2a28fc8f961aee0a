"""Shared model building blocks (pure JAX, dict-pytree params)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codr_linear import (PackedEmbedding, PackedLinear,  # noqa: F401
                                    dense_weight)
from repro.sharding import maybe_constrain

DEFAULT_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32      # master params; cast to compute dtype at use


def dense_init(key, d_in: int, d_out: int, *, scale: float | None = None,
               dtype=PARAM_DTYPE) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return jax.random.normal(key, (d_in, d_out), dtype) * scale


def embed_init(key, vocab: int, d: int, dtype=PARAM_DTYPE) -> jax.Array:
    return jax.random.normal(key, (vocab, d), dtype) * 0.02


def linear(x: jax.Array, w, b: jax.Array | None = None) -> jax.Array:
    """``x @ w (+ b)`` — the single matmul every model projection routes
    through.  A plain array executes as a dense ``jnp.dot``; a
    :class:`repro.core.codr_linear.PackedLinear` leaf (a params tree
    after ``repro.api.compile_params``) resolves through the backend
    registry and executes from the packed bitstream — the decode-fused
    transformer serving path (docs/DESIGN.md §2)."""
    if isinstance(w, PackedLinear):
        from repro.core import backends
        y = backends.resolve(w.backend).matmul(x, w)
    else:
        y = jnp.dot(x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def grouped_linear(x: jax.Array, w, group_sizes: jax.Array, *,
                   max_group: int | None = None) -> jax.Array:
    """Rows of ``x`` sorted by expert, ``group_sizes[e]`` of them for
    expert ``e``, each times its expert's matrix of the ``(E, K, N)``
    stack ``w`` — the expert matmul every MoE layer routes through.  A
    plain array is ``lax.ragged_dot``; a packed stack
    (:class:`repro.core.codr_linear.PackedLinear` with a leading expert
    dim) resolves through the backend registry's ``grouped_matmul`` and
    executes from the packed bitstream.  ``max_group`` bounds the rows
    any one expert holds (the tokens, when each picks distinct
    experts)."""
    if isinstance(w, PackedLinear):
        from repro.core import backends
        return backends.resolve(w.backend).grouped_matmul(
            x, group_sizes, w, max_group=max_group)
    return jax.lax.ragged_dot(x, w.astype(x.dtype), group_sizes)


def embedding_lookup(table, tokens: jax.Array,
                     dtype=DEFAULT_DTYPE) -> jax.Array:
    """``table[tokens]`` — the embedding gather every model routes
    through.  A plain ``(V, d)`` array is a ``jnp.take``; a
    :class:`repro.core.codr_linear.PackedEmbedding` leaf resolves
    through the backend registry and gathers *packed rows*, decoding
    only the tokens actually requested (docs/DESIGN.md §2.2)."""
    if isinstance(table, PackedEmbedding):
        from repro.core import backends
        return backends.resolve(table.backend).gather(tokens, table
                                                      ).astype(dtype)
    return jnp.take(table, tokens, axis=0).astype(dtype)


def unembed(x: jax.Array, table) -> jax.Array:
    """``x @ table.T`` — the logit projection against the (possibly
    packed) output embedding."""
    if isinstance(table, PackedEmbedding):
        from repro.core import backends
        return backends.resolve(table.backend).unembed(x, table)
    return jnp.dot(x, table.T.astype(x.dtype))


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
             f32: bool = True) -> jax.Array:
    if f32:
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
        return (y * w.astype(jnp.float32)).astype(x.dtype)
    # §Perf lever: f32 only in the (…,1) reduction accumulators — no
    # (B,S,D)-sized f32 tensor is ever materialized, forward or backward
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                   dtype=jnp.float32)
    r = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * r * w.astype(x.dtype)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-5, f32: bool = True) -> jax.Array:
    if f32:
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * w.astype(jnp.float32)
                + b.astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                   dtype=jnp.float32) - jnp.square(mu)
    r = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return ((x - mu.astype(x.dtype)) * r * w.astype(x.dtype)
            + b.astype(x.dtype))


def norm_apply(x, p, kind: str, f32: bool = True):
    if kind == "rmsnorm":
        return rms_norm(x, p["w"], f32=f32)
    return layer_norm(x, p["w"], p["b"], f32=f32)


def norm_init(d: int, kind: str):
    if kind == "rmsnorm":
        return {"w": jnp.ones((d,), PARAM_DTYPE)}
    return {"w": jnp.ones((d,), PARAM_DTYPE), "b": jnp.zeros((d,), PARAM_DTYPE)}


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, scaling=None) -> np.ndarray:
    """Rotary inverse frequencies of a ``head_dim`` rotation.  With a
    :class:`repro.configs.base.YarnScaling`, DeepSeek-V2's YaRN blend:
    dims below the correction dim of ``beta_fast`` rotations keep the
    plain frequency, dims above that of ``beta_slow`` take it over
    ``factor``, and a linear ramp joins them."""
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    if scaling is None:
        return freqs
    s = scaling

    def corr(rotations):
        return head_dim * math.log(s.original_max_position_embeddings
                                   / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(s.beta_fast)), 0)
    high = min(math.ceil(corr(s.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / s.factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(head_dim: int, scaling=None) -> float:
    """``head_dim ** -0.5``, times ``m(mscale_all_dim) ** 2`` under
    YaRN (DeepSeek-V2's ``softmax_scale``)."""
    scale = 1.0 / math.sqrt(head_dim)
    if scaling is not None and scaling.mscale_all_dim:
        scale *= yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2
    return scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: (B, S, H, D) — rotate the full head dim (halves, not
    interleaved pairs).  Under YaRN, cos and sin are scaled by
    ``m(mscale) / m(mscale_all_dim)``."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta, scaling), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: jax.Array | None = None) -> jax.Array:
    """Token-mean cross entropy; logits (.., V) f32-stable."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def constrain_tokens(x: jax.Array) -> jax.Array:
    """(B, S, D) activations: batch over data axes."""
    return maybe_constrain(x, "batch", None, None)
