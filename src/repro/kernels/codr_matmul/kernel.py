"""Pallas TPU kernel: CoDR unique-index compressed matmul.

``y = x @ decode(packed, table) * scale``

TPU adaptation of the CoDR PU (docs/DESIGN.md §2): the compressed weight
stream lives in HBM at ``bits/8`` bytes per weight; each grid step DMAs
one packed block into VMEM, decodes it with vector shifts + a masked
table reduction (the "Weight Decoder"), and feeds the dense tile to the
MXU.  The output tile is **output-stationary** in a VMEM scratch
accumulator across the K loop (the APE), and the activation tile is
reused across the N loop (the shared Input RF) — the paper's loop
ordering with HBM⇄VMEM standing in for SRAM⇄RF.

Weight layout: ``packed[k, w]`` uint32 words, each holding the ``b``-bit
indices of ``pw = 32 // b`` neighbouring columns ``w*pw + s`` at shift
``s*b``; ``table[2**b]`` sorted unique values, per-tensor ``scale``.

Decoding shift ``s`` of a word block gives the indices of columns
``w*pw + s`` — a lane-dense ``(bk, bw)`` plane — so the kernel never
interleaves lanes: it accumulates one MXU dot per plane into a
``(pw, bm, bw)`` plane-major accumulator, and the wrapper restores the
column order with one XLA transpose of the ``(m, N)`` output.  The table
and scale sit in SMEM, so every table entry is a scalar load.

Grid: ``(M//bm, Nw//bw, K//bk)`` — K innermost so the accumulator stays
resident; N next so the x-block is revisited (input semi-stationary);
M outermost (outputs written exactly once — "fully output stationary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128          # TPU vector lane width: word blocks are multiples


def _decode_plane(words: jax.Array, table_ref, shift: int,
                  bits: int) -> jax.Array:
    """Indices at ``shift`` of an int32 word block → their f32 table
    values (masked table reduction — no gather on the vector unit)."""
    idx = jax.lax.shift_right_logical(words, jnp.int32(shift)) \
        & jnp.int32((1 << bits) - 1)

    def body(u, acc):
        return acc + jnp.where(idx == u, table_ref[u], 0.0)

    return jax.lax.fori_loop(0, 1 << bits, body,
                             jnp.zeros(idx.shape, jnp.float32))


def _codr_matmul_kernel(x_ref, packed_ref, table_ref, scale_ref, o_ref,
                        acc_ref, *, bits: int, n_k: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x_blk = x_ref[...].astype(jnp.float32)
    words = packed_ref[...]
    for s in range(32 // bits):
        acc_ref[s] += jnp.dot(x_blk, _decode_plane(words, table_ref,
                                                   s * bits, bits),
                              preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[0]).astype(o_ref.dtype)


def _block(dim: int, target: int, align: int, *, divides: bool) -> int:
    """Block extent along one axis: the whole axis when it fits
    ``target``, else the largest multiple of ``align`` <= ``target``
    (that also divides ``dim`` when ``divides``, falling back to the
    whole axis).  Mosaic accepts a block dim that is a multiple of the
    tile or equal to the array dim."""
    if dim <= target:
        return dim
    b = max(target // align, 1) * align
    if divides:
        while b >= align and dim % b:
            b -= align
        return b if b >= align else dim
    return b


@functools.partial(jax.jit,
                   static_argnames=("bits", "n", "bm", "bn", "bk", "interpret"))
def codr_matmul_pallas(x: jax.Array, packed: jax.Array, table: jax.Array,
                       scale: jax.Array, *, bits: int, n: int,
                       bm: int = 128, bn: int = 2048, bk: int = 512,
                       interpret: bool = False) -> jax.Array:
    """``bm``/``bn``/``bk`` are block targets in rows, output columns and
    contraction rows; they are fitted to the TPU tiling: ``bk`` to a
    multiple of 128 dividing K (a ragged K block would sum padding),
    ``bn`` to a multiple of 128 words (ragged N/M edge blocks only
    compute columns/rows that are dropped)."""
    m, k = x.shape
    per_word = 32 // bits
    n_words = n // per_word
    assert packed.shape == (k, n_words), (packed.shape, (k, n_words))
    bm = _block(m, bm, 8, divides=False)
    bw = _block(n_words, max(bn // per_word, 1), LANES, divides=False)
    bk = _block(k, bk, LANES, divides=True)
    grid = (pl.cdiv(m, bm), pl.cdiv(n_words, bw), k // bk)

    kernel = functools.partial(_codr_matmul_kernel, bits=bits, n_k=grid[2])
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    planes = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),      # x: reused over j
            pl.BlockSpec((bk, bw), lambda i, j, kk: (kk, j)),
            smem,                                                  # table
            smem,                                                  # scale
        ],
        out_specs=pl.BlockSpec((per_word, bm, bw),
                               lambda i, j, kk: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((per_word, m, n_words), x.dtype),
        scratch_shapes=[pltpu.VMEM((per_word, bm, bw), jnp.float32)],
        interpret=interpret,
    )(x, jax.lax.bitcast_convert_type(packed, jnp.int32),
      table.astype(jnp.float32), scale.reshape(1).astype(jnp.float32))
    # plane s holds columns w*pw + s: restore the interleaved order
    return planes.transpose(1, 2, 0).reshape(m, n)
