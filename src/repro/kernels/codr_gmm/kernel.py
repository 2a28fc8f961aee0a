"""Pallas TPU kernel: grouped CoDR matmul over a packed expert stack.

``y[rows of e] = x[rows of e] @ decode(packed[e], table[e]) * scale[e]``

The rows of ``x`` come sorted by expert, ``group_sizes[e]`` of them for
expert ``e`` (a MoE layer's token copies after routing).  The weights
are the stack ``compile_params`` writes for an expert leaf: ``packed``
``(E, K, N * bits // 32)`` uint32 words in ``codr_matmul``'s layout, one
sorted table and one scale per expert.

Grid ``(E, row blocks, N // bw, K // bk)``, expert-major.  The group
starts ride in by scalar prefetch.  A grid step decodes its word block
once (``codr_matmul.kernel.decode_plane``) and applies it to every row
of its row block, so each packed block of an expert is decoded once per
call while the expert's rows fit one row block (``codr_matmul``'s rule,
:func:`repro.kernels.codr_matmul.kernel.row_blocks`, over the rows one
expert can hold).  An expert with no rows is skipped: its steps compute
nothing and its weight blocks' index map repeats the block already in
VMEM, so none of its words is read.

The row windows are dynamic, so rows move by hand: the wrapper lays the
groups out on sublane-aligned starts, and a step copies in only the
``SUB``-row slices its group fills and copies them out again.  Row
windows of neighbouring groups overlap; the copies run in grid order
and each is waited for, so the last write to any row is its own
group's.  The accumulator is plane-major as in ``codr_matmul``; the
wrapper restores the column order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.codr_matmul.kernel import (LANES, VMEM_LIMIT, _block,
                                              _row_block, block_targets,
                                              decode_plane, k_block,
                                              row_blocks)

ALIGN = 8            # group starts on the f32 sublane tile
SUB = 128            # rows of one copy and one dot: only filled ones run


def _sub_blocks(bm: int) -> list[tuple[int, int]]:
    """``(offset, rows)`` of the ``SUB``-row slices of a row block."""
    return [(o, min(SUB, bm - o)) for o in range(0, bm, SUB)]


def _gmm_kernel(starts_ref, sizes_ref, widx_ref, wlast_ref, x_hbm,
                packed_ref, table_ref, scale_ref, o_hbm, xs_ref, acc_ref,
                sem, *, bits: int, n_k: int, bm: int, bk: int, bw: int,
                bw_pad: int):
    del widx_ref, wlast_ref                 # read by the index map only
    e, r = pl.program_id(0), pl.program_id(1)
    j, kk = pl.program_id(2), pl.program_id(3)
    size = sizes_ref[e] - r * bm
    start = pl.multiple_of(starts_ref[e] + r * bm, ALIGN)
    subs = _sub_blocks(bm)
    cols = slice(None) if bw == bw_pad else pl.ds(0, bw)

    def filled(off):
        return off < size

    @pl.when(size > 0)
    def _step():
        @pl.when(j == 0)
        def _load():                        # this K slice of the rows
            for off, n in subs:
                @pl.when(filled(off))
                def _copy():
                    cp = pltpu.make_async_copy(
                        x_hbm.at[pl.ds(start + off, n), pl.ds(kk * bk, bk)],
                        xs_ref.at[kk, pl.ds(off, n)], sem)
                    cp.start()
                    cp.wait()

        @pl.when(kk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        words = packed_ref[...]
        for p in range(32 // bits):
            w = decode_plane(words, lambda u: table_ref[e, u], p * bits,
                             bits)
            for off, n in subs:
                @pl.when(filled(off))
                def _dot():
                    acc_ref[p, pl.ds(off, n), cols] += jnp.dot(
                        xs_ref[kk, pl.ds(off, n)], w,
                        preferred_element_type=jnp.float32)

        @pl.when(kk == n_k - 1)
        def _store():
            acc_ref[...] = acc_ref[...] * scale_ref[e]
            for off, n in subs:
                @pl.when(filled(off))
                def _copy():
                    cp = pltpu.make_async_copy(
                        acc_ref.at[:, pl.ds(off, n)],
                        o_hbm.at[:, pl.ds(start + off, n),
                                 pl.ds(j * bw_pad, bw_pad)], sem)
                    cp.start()
                    cp.wait()


def layout(group_sizes: jax.Array, m: int, bm: int):
    """Aligned group starts, and each sorted row's row in that layout
    (rows past the groups' total go nowhere: ``m_tot``), and ``m_tot``,
    the rows the layout holds with one row block of room at its end."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    padded = (sizes + ALIGN - 1) // ALIGN * ALIGN
    starts = jnp.cumsum(padded) - padded
    ends = jnp.cumsum(sizes)
    # each row's group: how many group ends lie at or before it
    gid = jnp.cumsum(jnp.zeros((m + 1,), jnp.int32).at[ends].add(
        1, mode="drop"))[:m]
    rows = jnp.arange(m, dtype=jnp.int32)
    inside = gid < e
    gid = jnp.minimum(gid, e - 1)
    m_tot = -(-(m + (ALIGN - 1) * e + bm) // ALIGN) * ALIGN
    dest = jnp.where(inside, starts[gid] + rows - (ends[gid] - sizes[gid]),
                     m_tot)
    return starts, dest, m_tot


def _weight_source(sizes: jax.Array):
    """For each expert, whose weight blocks its skipped steps point at:
    its own last (an expert with rows), the last of the nearest earlier
    expert with rows, else the first of the nearest later one — always
    the block already in VMEM, so a skipped step reads no words."""
    e = sizes.shape[0]
    idx = jnp.arange(e, dtype=jnp.int32)
    has = (sizes > 0)[None, :]
    before = idx[None, :] <= idx[:, None]              # [e, e']: e' <= e
    prev = jnp.where(has & before, idx[None, :], -1).max(axis=1)
    nxt = jnp.where(has & ~before, idx[None, :], e).min(axis=1)
    widx = jnp.where(prev >= 0, prev, jnp.minimum(nxt, e - 1))
    return widx.astype(jnp.int32), (prev >= 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bits", "n", "max_group",
                                             "interpret"))
def codr_gmm_pallas(x: jax.Array, group_sizes: jax.Array, packed: jax.Array,
                    table: jax.Array, scale: jax.Array, *, bits: int, n: int,
                    max_group: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """``x (m, K)`` rows sorted by expert, ``group_sizes (E,)``, packed
    words ``(E, K, n * bits // 32)``, ``table (E, 2**bits)``, ``scale
    (E,)`` → ``(m, n)`` float32.  Rows past ``group_sizes``' total come
    out 0.  ``max_group`` bounds any one group (default ``m``) and sets
    the row block."""
    m, k = x.shape
    e = group_sizes.shape[0]
    pw = 32 // bits
    n_words = n // pw
    assert packed.shape == (e, k, n_words), (packed.shape, (e, k, n_words))
    g = m if max_group is None else max(min(max_group, m), 1)
    bm = -(-_row_block(g) // ALIGN) * ALIGN
    n_r = row_blocks(g)
    bn, bkt = block_targets(bm)
    bw = _block(n_words, max(bn // pw, 1), LANES, divides=True)
    bk = k_block(k, bkt)
    n_j, n_k = n_words // bw, -(-k // bk)
    # a word block narrower than the lanes (the whole N, one block) sits
    # in lane-padded VMEM: the accumulator and the output carry the pad
    bw_pad = -(-bw // LANES) * LANES

    sizes = group_sizes.astype(jnp.int32)
    starts, dest, m_tot = layout(sizes, m, bm)
    # K padded with zeros to whole blocks (a ragged last word block)
    xp = jnp.zeros((m_tot, n_k * bk), jnp.float32).at[dest, :k].set(
        x.astype(jnp.float32), mode="drop")
    widx, wlast = _weight_source(sizes)

    def w_map(ei, r, j, kk, starts_ref, sizes_ref, widx_ref, wlast_ref):
        live = r * bm < sizes_ref[ei]
        last = wlast_ref[ei] > 0
        return (jnp.where(live, ei, widx_ref[ei]),
                jnp.where(live, kk, jnp.where(last, n_k - 1, 0)),
                jnp.where(live, j, jnp.where(last, n_j - 1, 0)))

    kernel = functools.partial(_gmm_kernel, bits=bits, n_k=n_k, bm=bm,
                               bk=bk, bw=bw, bw_pad=bw_pad)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    planes = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(e, n_r, n_j, n_k),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),          # x rows
                      pl.BlockSpec((None, bk, bw), w_map),          # words
                      smem,                                         # tables
                      smem],                                        # scales
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((n_k, bm, bk), jnp.float32),
                            pltpu.VMEM((pw, bm, bw_pad), jnp.float32),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((pw, m_tot, n_j * bw_pad),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="codr_gmm",
    )(starts, sizes, widx, wlast, xp,
      jax.lax.bitcast_convert_type(packed, jnp.int32),
      table.astype(jnp.float32), scale.reshape(e).astype(jnp.float32))
    # plane p holds columns w*pw + p; rows back to their sorted order
    y = planes[..., :n_words].transpose(1, 2, 0).reshape(m_tot, n)
    return jnp.take(y, dest, axis=0, mode="fill", fill_value=0.0)
