"""The served parameters of a packed language model, made on the device
in one jitted call from the seed, in the types ``compile_params``
returns.

``compile_params``' own rules pick each leaf's form from
``jax.eval_shape(init_params)``: a leaf whose path holds an
``EMBED_INCLUDE`` token becomes a ``PackedEmbedding``, one holding a
``PACK_INCLUDE`` token a ``PackedLinear`` (per-matrix packs stacked over
the layers, one table and scale per leaf, as ``compile_params`` stacks
them); every other leaf stays dense.  The values come from
``bench.lib.lm_weights``, so the plain reference can rebuild them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import lm_weights as lw


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def make_params(cfg, seed: int, *, bits: int = 4,
                backend: str = "codr_matmul"):
    """Packed params of ``cfg`` from ``seed``, on the default device."""
    from repro.core import api, serving
    from repro.core.codr_linear import (PackedEmbedding, PackedLinear,
                                        PackedWeight)
    from repro.models import get_model

    shapes = jax.eval_shape(
        lambda: get_model(cfg).init_params(jax.random.PRNGKey(0), cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    n_levels = 1 << bits

    def build(key):
        leaves = []
        for path, leaf in flat:
            p = _path(path)
            k = lw.leaf_key(key, p)
            stacked = p.startswith("stack/")
            if leaf.ndim < 2 or leaf.size < serving.MIN_COMPRESS_SIZE \
                    or not (any(t in p for t in api.PACK_INCLUDE)
                            or any(t in p for t in api.EMBED_INCLUDE)):
                if stacked:
                    arr = jax.vmap(lambda l: lw.dense(
                        k, l, leaf.shape[1:], lw.dense_kind(p)))(
                            jnp.arange(leaf.shape[0]))
                else:
                    arr = lw.dense(k, 0, leaf.shape, lw.dense_kind(p))
                leaves.append(arr.astype(leaf.dtype))
                continue
            kk, n = leaf.shape[-2:]
            tbl = lw.table(k, n_levels)
            if any(t in p for t in api.EMBED_INCLUDE):
                pw = PackedWeight(lw.words(k, 0, kk, n, bits), tbl,
                                  lw.scale(tbl, lw.EMBED_STD), bits, (kk, n))
                leaves.append(PackedEmbedding(pw, d_model=n,
                                              backend=backend))
                continue
            s = lw.scale(tbl, 1.0 / float(kk) ** 0.5)
            if stacked:
                n_l = leaf.shape[0]
                packed = jax.vmap(lambda l: lw.words(k, l, kk, n, bits))(
                    jnp.arange(n_l))
                tbl = jnp.broadcast_to(tbl, (n_l, n_levels))
                s = jnp.broadcast_to(s, (n_l,))
            else:
                packed = lw.words(k, 0, kk, n, bits)
            leaves.append(PackedLinear(PackedWeight(packed, tbl, s, bits,
                                                    (kk, n)),
                                       out_features=n, backend=backend))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(lw.seed_key(seed))
