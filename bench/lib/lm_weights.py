"""Random weights of a packed language model, drawn on the device from
a seed.  Program-free: both the served pack and the plain reference are
made from these functions, each leaf and each layer from a key of its
own, so the reference can rebuild any one layer without the rest.

A packed leaf is ``bits``-bit indices into a sorted table of distinct
int8 levels, symmetric about 0, with one scale per tensor; index ``s`` of word ``w`` in row
``r`` is column ``w * (32 // bits) + s`` at bit offset ``s * bits``.
Its scale makes the weights' root mean square ``1 / sqrt(K)`` for a
``(K, N)`` projection and ``0.02`` for an embedding table.  Leaves that
are not packed are norm gains (``1 + 0.1 * normal``) or biases
(``0.02 * normal``).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

INT8_LEVELS = 127
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed below 2**64."""
    k = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 32) & 0xFFFFFFFF)


def leaf_key(key: jax.Array, path: str) -> jax.Array:
    """The key of the leaf at ``path`` (``"stack/b0/mixer/q_proj"``)."""
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def table(key: jax.Array, n_levels: int) -> jax.Array:
    """``n_levels`` distinct int8 levels, sorted, as float32: distinct
    magnitudes with both signs, so the table's mean is 0.  (A table
    with a mean gives every weight matrix a rank-one part that piles up
    along the residual stream over 36 layers.)"""
    mags = jax.random.choice(
        jax.random.fold_in(key, 1), jnp.arange(1, INT8_LEVELS + 1),
        (n_levels // 2,), replace=False)
    return jnp.sort(jnp.concatenate([-mags, mags])).astype(jnp.float32)


def words(key: jax.Array, layer, k: int, n: int, bits: int) -> jax.Array:
    """Layer ``layer``'s ``(k, n * bits // 32)`` uint32 index words."""
    assert (n * bits) % 32 == 0, (n, bits)
    sub = jax.random.fold_in(jax.random.fold_in(key, 0), layer)
    return jax.random.bits(sub, (k, n * bits // 32), jnp.uint32)


def scale(tbl: jax.Array, std) -> jax.Array:
    """Per-tensor scale giving the decoded weights r.m.s. ``std``."""
    return (std / jnp.sqrt(jnp.mean(tbl * tbl))).astype(jnp.float32)


def dense(key: jax.Array, layer, shape, kind: str) -> jax.Array:
    """A leaf that is not packed: a norm gain or a bias."""
    z = jax.random.normal(jax.random.fold_in(key, layer), shape,
                          jnp.float32)
    return 1.0 + 0.1 * z if kind == "norm" else 0.02 * z


def dense_kind(path: str) -> str:
    return "norm" if "norm" in path else "bias"


def unpack(w: jax.Array, bits: int) -> jax.Array:
    """``(k, n * bits // 32)`` words → ``(k, n)`` int32 indices."""
    per_word = 32 // bits
    shifts = jnp.arange(per_word, dtype=jnp.uint32) * bits
    idx = (w[..., None] >> shifts) & jnp.uint32((1 << bits) - 1)
    return idx.reshape(*w.shape[:-1], w.shape[-1] * per_word
                       ).astype(jnp.int32)


def decode(w: jax.Array, tbl: jax.Array, s: jax.Array, bits: int
           ) -> jax.Array:
    """Dense float32 weights of one packed matrix."""
    return jnp.take(tbl, unpack(w, bits)) * s
