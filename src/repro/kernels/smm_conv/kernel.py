"""Pallas kernel: CoDR scalar–matrix-multiplication convolution.

This is the *faithful-mechanism* kernel — the MPE/APE datapath of paper
Fig. 5c expressed as a TPU kernel (run in interpret mode on CPU; the MXU
kernel in :mod:`repro.kernels.codr_matmul` is the performance path, see
docs/DESIGN.md §2):

* **Phase A — MPE / differential MLP array**: a ``fori_loop`` over the
  unique weights performs ``P[u] = P[u-1] + Δ[u] * X`` — the Matrix-Matrix
  Accumulator adding the Δ-multiplication result to the prior product
  (paper Eq. 1).  One scalar×matrix multiply per *unique* weight: weight
  sparsity, repetition, and similarity are all exploited here.
* **Phase B — crossbar + APE**: a ``fori_loop`` over the repetition
  entries routes a ``(RO, CO)`` window of the selected product ``P[u]``
  into the output accumulator of its output channel (dynamic slice +
  dynamic store = the interconnection network).  A convolution stride
  becomes a *strided* window load (``pl.ds(r, ro, stride)``) — the
  crossbar skips feature columns instead of the ALUs doing extra work.

Grid ``(B, m_tiles, N)``: the whole batch is dispatched by one kernel
call (batched SMM dispatch — no per-sample Python loop); per (batch,
tile) the output stays stationary in VMEM scratch across the
input-channel loop (output stationary) while the input plane block is the
Input-RF broadcast.

Operand layout (built offline by ``pack_smm_operands`` from the UCR/RLE
decode — static shapes, padded, packed once per layer):

* ``x``       (B, N, RI, CI)          input feature batch
* ``deltas``  (m_tiles, N, U+1)       unique-weight Δs (padded 0)
* ``entries`` (m_tiles, N, L, 4)      (u, m_local, r, c) per repetition;
                                      padding points at the zero product
                                      row ``u = U`` and m_local = 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _smm_conv_kernel(x_ref, deltas_ref, entries_ref, o_ref, acc_ref, p_ref,
                     *, n_in: int, u_max: int, l_max: int, ro: int, co: int,
                     stride: int):
    n_step = pl.program_id(2)

    @pl.when(n_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0, 0].astype(jnp.float32)                    # (RI, CI)

    # -- Phase A: differential scalar–matrix multiplies (MPE array) --------
    p_ref[u_max, :, :] = jnp.zeros_like(x)                 # zero product row

    def mpe(u, carry):
        prod = carry + deltas_ref[0, 0, u].astype(jnp.float32) * x
        p_ref[u, :, :] = prod
        return prod

    jax.lax.fori_loop(0, u_max, mpe, jnp.zeros_like(x))

    # -- Phase B: crossbar routing + APE accumulation ----------------------
    def ape(l, _):
        u = entries_ref[0, 0, l, 0]
        m_loc = entries_ref[0, 0, l, 1]
        r = entries_ref[0, 0, l, 2]
        c = entries_ref[0, 0, l, 3]
        window = p_ref[pl.ds(u, 1), pl.ds(r, ro, stride),
                       pl.ds(c, co, stride)]
        acc_ref[pl.ds(m_loc, 1), :, :] += window
        return 0

    jax.lax.fori_loop(0, l_max, ape, 0)

    @pl.when(n_step == n_in - 1)
    def _done():
        o_ref[...] = acc_ref[...][None].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("t_m", "ro", "co", "stride", "interpret"))
def smm_conv_pallas(x: jax.Array, deltas: jax.Array, entries: jax.Array,
                    *, t_m: int, ro: int, co: int, stride: int = 1,
                    interpret: bool = False) -> jax.Array:
    """Batched SMM convolution: ``x`` (B, N, RI, CI) → (B, m_tiles·t_m,
    RO, CO).  One compiled kernel call covers the whole batch."""
    b, n_in, ri, ci = x.shape
    m_tiles, n2, u_plus = deltas.shape
    assert n2 == n_in
    l_max = entries.shape[2]
    u_max = u_plus - 1

    kernel = functools.partial(_smm_conv_kernel, n_in=n_in, u_max=u_max,
                               l_max=l_max, ro=ro, co=co, stride=stride)
    return pl.pallas_call(
        kernel,
        grid=(b, m_tiles, n_in),
        in_specs=[
            pl.BlockSpec((1, 1, ri, ci), lambda bb, i, n: (bb, n, 0, 0)),
            pl.BlockSpec((1, 1, u_plus), lambda bb, i, n: (i, n, 0)),
            pl.BlockSpec((1, 1, l_max, 4), lambda bb, i, n: (i, n, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t_m, ro, co),
                               lambda bb, i, n: (bb, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m_tiles * t_m, ro, co),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((t_m, ro, co), jnp.float32),        # APE accumulators
            pltpu.VMEM((u_plus, ri, ci), jnp.float32),     # MPE product rows
        ],
        interpret=interpret,
    )(x, deltas, entries)
