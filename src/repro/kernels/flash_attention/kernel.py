"""Pallas TPU kernel: fused flash attention.

This is the fix for attention's dominant memory term (methodology in
``docs/DESIGN.md`` §6): the XLA-compiled attention materializes every
(q_block × kv_block) score tile in HBM (B·H·S² traffic); the fused
kernel keeps score tiles, the online-softmax stats, and the output
accumulator **in VMEM** — HBM traffic collapses to q/k/v reads + o
writes (the theoretical floor).

Grid ``(B·H, n_q, n_k)`` with the kv loop innermost: the (bq, D)
accumulator and (bq,) running max/denominator live in VMEM scratch
across the kv sweep (output-stationary, same loop discipline as the
CoDR matmul kernel).  Causal masking by absolute block positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, bq: int, bk: int, n_k: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (bq, D)
    k = k_ref[0].astype(jnp.float32)              # (bk, D)
    v = v_ref[0].astype(jnp.float32)              # (bk, Dv)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[0, ...] = (acc_ref[...]
                         / jnp.maximum(l_ref[...], 1e-30)[:, None]
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "scale",
                                    "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 512,
                           bk: int = 512, scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """q/k/v: (BH, S, D) — batch·heads flattened (GQA grouping done by
    the ops wrapper)."""
    bh, sq, d = q.shape
    _, sk, dv = v.shape
    # snap block sizes to divisors of S (padding blocks would otherwise
    # inject garbage keys into the softmax)
    bq = min(bq, sq)
    while sq % bq:
        bq -= 1
    bk = min(bk, sk)
    while sk % bk:
        bk -= 1
    scale = scale if scale is not None else d ** -0.5
    grid = (bh, pl.cdiv(sq, bq), pl.cdiv(sk, bk))
    kernel = functools.partial(_flash_kernel, causal=causal, bq=bq, bk=bk,
                               n_k=grid[2], scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # denominator
            pltpu.VMEM((bq, dv), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
