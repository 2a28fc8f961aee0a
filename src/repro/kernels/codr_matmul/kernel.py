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
resident; N next so the x-block is revisited (input semi-stationary).
Each grid step decodes its word block anew, so a call decodes every
packed block once per row block: the row block covers the whole call
(``bm = M``) up to ``ROW_CAP`` rows, and a longer call splits into equal
row blocks of at most ``ROW_CAP`` (:func:`row_blocks`).  The decoded
tile is then reused by every row of the call (input stationary, the
paper's reuse applied to the decoded weights).  Above 128 rows the word
blocks are narrower than the pooled decode step's (:func:`_blocks`), and
every call asks for ``VMEM_LIMIT`` of scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128          # TPU vector lane width: word blocks are multiples
ROW_CAP = 1024       # most rows one block holds (the longest chat prompt)
VMEM_LIMIT = 64 << 20        # scoped VMEM a call asks for (a v5e has 128 MiB)


def decode_plane(words: jax.Array, level, shift: int,
                 bits: int) -> jax.Array:
    """Indices at ``shift`` of an int32 word block → their f32 table
    values, ``level(u)`` being entry ``u`` as a scalar (masked table
    reduction — no gather on the vector unit)."""
    idx = jax.lax.shift_right_logical(words, jnp.int32(shift)) \
        & jnp.int32((1 << bits) - 1)

    def body(u, acc):
        return acc + jnp.where(idx == u, level(u), 0.0)

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
        w = decode_plane(words, lambda u: table_ref[u], s * bits, bits)
        acc_ref[s] += jnp.dot(x_blk, w, preferred_element_type=jnp.float32)

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


def k_block(k: int, target: int) -> int:
    """Contraction rows of a block: the largest multiple of 128 up to
    ``target`` that divides K; where none does (DeepSeek-V2-Lite's dense
    FFN, K = 10,944 = 64 x 171) the target itself, the last block then
    ragged: the wrapper pads the activations' K with zeros, so the rows
    past K of that word block (whatever words, each decoding to a finite
    table value) add nothing."""
    b = _block(k, target, LANES, divides=True)
    return max(target // LANES, 1) * LANES if b == k > target else b


def _row_block(m: int) -> int:
    """The whole call up to ``ROW_CAP`` rows, else equal blocks of at
    most ``ROW_CAP`` rows, rounded up to the sublane tile of 8."""
    if m <= ROW_CAP:
        return m
    n = -(-m // ROW_CAP)
    return -(-m // (8 * n)) * 8


def row_blocks(m: int) -> int:
    """Row blocks of an ``m``-row call with the default blocks: how many
    times the call decodes each packed weight block."""
    return -(-m // _row_block(m))


def vmem_bytes(bm: int, bw: int, bk: int, bits: int) -> int:
    """VMEM one call's blocks take, all counted as f32 (the widest the
    kernel sees): double-buffered x, word and output blocks, the
    accumulator, and the index plane, decoded plane and dot of one plane
    in flight."""
    pw = 32 // bits
    return 4 * (2 * bm * bk + 2 * bk * bw + 3 * pw * bm * bw
                + 2 * bk * bw + bm * bw)


def block_targets(rows: int) -> tuple[int, int]:
    """Output-column and contraction-row targets of the word block that
    ``rows`` rows share: 2048 x 512 up to 128 rows, 1024 x 256 above."""
    return (2048, 512) if rows <= 128 else (1024, 256)


def _blocks(m: int, k: int, n_words: int, bits: int) -> tuple[int, int, int]:
    """Default ``(bm, bw, bk)`` of an ``(m, k) x (k, n_words)`` call:
    ``bm`` from :func:`_row_block`; ``bw`` and ``bk`` fitted to targets
    of 2048 output columns and 512 contraction rows up to 128 rows (the
    pooled decode step's blocks), and of 1024 and 256 above, where the
    quarter-size word block decoded 4-40% faster per call (TPU v5e,
    qwen2.5-3b projections at 256-1024 rows).  Every width fits
    ``VMEM_LIMIT`` at ``ROW_CAP`` rows (:func:`vmem_bytes`)."""
    bn, bk = block_targets(m)
    return (_row_block(m),
            _block(n_words, max(bn // (32 // bits), 1), LANES, divides=False),
            k_block(k, bk))


@functools.partial(jax.jit,
                   static_argnames=("bits", "n", "bm", "bn", "bk", "interpret"))
def codr_matmul_pallas(x: jax.Array, packed: jax.Array, table: jax.Array,
                       scale: jax.Array, *, bits: int, n: int,
                       bm: int | None = None, bn: int | None = None,
                       bk: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """``bm``/``bn``/``bk`` are block targets in rows, output columns and
    contraction rows, each defaulting to :func:`_blocks`' choice; a
    target given is fitted to the TPU tiling: ``bk`` to a multiple of 128
    dividing K (a ragged K block would sum padding), ``bn`` to a multiple
    of 128 words (ragged N/M edge blocks only compute columns/rows that
    are dropped)."""
    m, k = x.shape
    per_word = 32 // bits
    n_words = n // per_word
    assert packed.shape == (k, n_words), (packed.shape, (k, n_words))
    d_bm, d_bw, d_bk = _blocks(m, k, n_words, bits)
    bm = d_bm if bm is None else _block(m, bm, 8, divides=False)
    bw = d_bw if bn is None else _block(n_words, max(bn // per_word, 1),
                                        LANES, divides=False)
    bk = d_bk if bk is None else k_block(k, bk)
    grid = (pl.cdiv(m, bm), pl.cdiv(n_words, bw), pl.cdiv(k, bk))
    if k % bk:                      # a ragged last K block sums zeros
        x = jnp.pad(x, ((0, 0), (0, grid[2] * bk - k)))

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
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(x, jax.lax.bitcast_convert_type(packed, jnp.int32),
      table.astype(jnp.float32), scale.reshape(1).astype(jnp.float32))
    # plane s holds columns w*pw + s: restore the interleaved order
    return planes.transpose(1, 2, 0).reshape(m, n)
