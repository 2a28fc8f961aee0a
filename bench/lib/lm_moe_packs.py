"""The served parameters of a packed mixture-of-experts language model,
made on the device from the seed, in the types ``compile_params``
returns — ``bench.lib.lm_packs`` with expert stacks.

The leaf rules are ``lm_packs``': a leaf whose path holds an
``EMBED_INCLUDE`` token becomes a ``PackedEmbedding``, one holding a
``PACK_INCLUDE`` token a ``PackedLinear`` (per-matrix packs stacked
over the layers and, for an expert leaf ``(L, E, K, N)``, over the
experts; one table and scale per leaf, as ``compile_params`` stacks
them); every other leaf stays dense.  Matrix ``e`` of layer ``l`` of an
expert leaf draws its words as matrix ``l * E + e`` of
``bench.lib.lm_weights``, so the plain reference can rebuild any one
expert.  Each leaf is made by a program of its own, and an expert leaf
one layer at a time, so that building holds little beside the packs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import lm_weights as lw
from bench.lib.lm_packs import _path


def expert_words(key, layer, e: int, kk: int, n: int, bits: int):
    """Layer ``layer``'s ``(E, kk, n * bits // 32)`` words of an expert
    leaf."""
    return jax.vmap(lambda i: lw.words(key, layer * e + i, kk, n, bits))(
        jnp.arange(e))


def _leaf(key, p: str, shape, dtype, bits: int, backend: str):
    from repro.core import api, serving
    from repro.core.codr_linear import (PackedEmbedding, PackedLinear,
                                        PackedWeight)
    k = lw.leaf_key(key, p)
    n_levels = 1 << bits
    packs = any(t in p for t in api.PACK_INCLUDE)
    embeds = any(t in p for t in api.EMBED_INCLUDE)
    if len(shape) < 2 or _size(shape) < serving.MIN_COMPRESS_SIZE \
            or not (packs or embeds):
        if p.startswith("stack/"):
            return jax.jit(lambda: jax.vmap(lambda l: lw.dense(
                k, l, shape[1:], lw.dense_kind(p)))(
                    jnp.arange(shape[0])).astype(dtype))()
        return jax.jit(lambda: lw.dense(k, 0, shape, lw.dense_kind(p))
                       .astype(dtype))()
    kk, n = shape[-2:]
    tbl = lw.table(k, n_levels)
    if embeds:
        pw = PackedWeight(jax.jit(lambda: lw.words(k, 0, kk, n, bits))(),
                          tbl, lw.scale(tbl, lw.EMBED_STD), bits, (kk, n))
        return PackedEmbedding(pw, d_model=n, backend=backend)
    s = lw.scale(tbl, 1.0 / float(kk) ** 0.5)
    lead = tuple(shape[:-2])
    if len(lead) == 2:                       # (L, E): experts by layer
        n_l, e = lead
        packed = jax.jit(lambda: jax.lax.map(
            lambda l: expert_words(k, l, e, kk, n, bits),
            jnp.arange(n_l)))()
    elif lead:
        packed = jax.jit(lambda: jax.vmap(
            lambda l: lw.words(k, l, kk, n, bits))(jnp.arange(lead[0])))()
    else:
        packed = jax.jit(lambda: lw.words(k, 0, kk, n, bits))()
    tbl = jnp.broadcast_to(tbl, lead + (n_levels,))
    s = jnp.broadcast_to(s, lead)
    return PackedLinear(PackedWeight(packed, tbl, s, bits, (kk, n)),
                        out_features=n, backend=backend)


def _size(shape) -> int:
    out = 1
    for d in shape:
        out *= d
    return out


def make_params(cfg, seed: int, *, bits: int = 4,
                backend: str = "codr_matmul"):
    """Packed params of ``cfg`` from ``seed``, on the default device."""
    from repro.models import get_model

    shapes = jax.eval_shape(
        lambda: get_model(cfg).init_params(jax.random.PRNGKey(0), cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = lw.seed_key(seed)
    leaves = [_leaf(key, _path(path), leaf.shape, leaf.dtype, bits, backend)
              for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)
