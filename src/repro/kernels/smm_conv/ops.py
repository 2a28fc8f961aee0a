"""Wrapper + offline operand packer for the SMM convolution kernel."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.smm import decode_index
from repro.core.ucr import LayerCode
from repro.kernels.smm_conv.kernel import smm_conv_pallas

# Capability facts consumed by the backend registry
# (repro.core.backends.SmmKernelBackend) — kept next to the kernel so the
# registry never hardcodes what a kernel can lower.
KERNEL_CAPS = {
    "kinds": ("conv",),            # this kernel only lowers convolutions
    "max_stride": None,            # native strided crossbar routing
    "integer_activations": True,   # 8-bit feature datapath (exact int math)
    "batched_grid": True,          # batch = leading grid dimension
    "interpret_on_cpu": True,
    "description": "Pallas MPE/APE SMM convolution (batched grid; "
                   "interpret mode off-TPU)",
}

# What the v5e compiler answers for this kernel at a VGG16 layer: the
# per-(tile, channel) delta and entry blocks are scalar tables, not
# (8, 128)-tiled vectors.  The backend reports this on a TPU.
TPU_REFUSAL = (
    "Mosaic refuses the smm_conv kernel: its (1, 1, U+1) delta block "
    "breaks the rule that 'the last two dimensions of your block shape "
    "are divisible by 8 and 128 respectively, or be equal to the "
    "respective dimensions of the overall array'")


def pack_smm_operands(code: LayerCode, n_in: int
                      ) -> tuple[np.ndarray, np.ndarray, dict]:
    """UCR vectors → padded static-shape kernel operands.

    Returns ``(deltas, entries, meta)``:
      deltas  (m_tiles, N, U_max+1) float32 — Δs of sorted unique weights
      entries (m_tiles, N, L_max, 4) int32 — (u, m_local, r, c) per
              repetition; padding → (U_max, 0, 0, 0) = zero product row.
    """
    m = code.shape[0]
    rk, ck = (code.shape[2], code.shape[3]) if len(code.shape) == 4 else (1, 1)
    m_tiles = -(-m // code.t_m)
    u_max = max((len(u.unique_vals) for u in code.ucr), default=1) or 1
    l_max = max((len(u.indexes) for u in code.ucr), default=1) or 1

    deltas = np.zeros((m_tiles, n_in, u_max + 1), dtype=np.float32)
    entries = np.zeros((m_tiles, n_in, l_max, 4), dtype=np.int32)
    entries[:, :, :, 0] = u_max                     # point at the zero row

    for vi, u in enumerate(code.ucr):
        mt, nn = vi // n_in, vi % n_in
        vals = u.unique_vals.astype(np.float32)
        deltas[mt, nn, : len(vals)] = np.diff(vals, prepend=0.0)
        cursor = 0
        li = 0
        for ui, rep in enumerate(u.reps):
            for idx in u.indexes[cursor : cursor + int(rep)]:
                m_loc, r, c = decode_index(int(idx), (rk, ck))
                entries[mt, nn, li] = (ui, m_loc, r, c)
                li += 1
            cursor += int(rep)
    return deltas, entries, {"m_tiles": m_tiles, "t_m": code.t_m,
                             "u_max": u_max, "l_max": l_max}


def smm_conv_batched(x: jax.Array, code: LayerCode, *, stride: int = 1,
                     interpret: bool | None = None,
                     operands: tuple | None = None) -> jax.Array:
    """Batched CoDR SMM convolution: ``x`` (B, N, RI, CI) → (B, M, RO, CO).

    The whole batch runs in ONE Pallas dispatch (batch = leading grid
    dimension — no per-sample Python loop).  Pass ``operands`` (the
    ``(deltas, entries, meta)`` triple from :func:`pack_smm_operands`,
    device arrays) to reuse a layer's packed operands across calls — the
    engine caches them per layer; otherwise they are packed here.

    ``stride`` is routed into the kernel as strided crossbar window loads.
    Off-TPU the kernel runs in interpret mode; Mosaic refuses it
    (:data:`TPU_REFUSAL`), so the ``smm_kernel`` backend is not offered
    on a TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if stride < 1:
        raise ValueError(f"stride must be >= 1, got {stride}")
    _, n_in, ri, ci = x.shape
    rk, ck = (code.shape[2], code.shape[3]) if len(code.shape) == 4 else (1, 1)
    ro, co = (ri - rk) // stride + 1, (ci - ck) // stride + 1
    if operands is None:
        deltas, entries, meta = pack_smm_operands(code, n_in)
        deltas, entries = jnp.asarray(deltas), jnp.asarray(entries)
    else:
        deltas, entries, meta = operands
    y = smm_conv_pallas(jnp.asarray(x, jnp.float32), deltas, entries,
                        t_m=meta["t_m"], ro=ro, co=co, stride=stride,
                        interpret=interpret)
    return y[:, : code.shape[0]]


def smm_conv(x: jax.Array, code: LayerCode, *, stride: int = 1,
             interpret: bool | None = None) -> jax.Array:
    """CoDR SMM convolution of ``x`` (N, RI, CI) with an encoded layer.
    Returns pre-activation int-exact accumulations (float32), cropped to
    the true output-channel count."""
    return smm_conv_batched(x[None], code, stride=stride,
                            interpret=interpret)[0]
