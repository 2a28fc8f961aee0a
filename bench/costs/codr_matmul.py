"""Work of one ``codr_matmul`` call ``y (m, n) = x (m, k) @ W (k, n)``
with ``W`` stored as ``bits``-bit indices packed in uint32 words.

Counted from the layer, not from the kernel's loops: 2*m*k*n operations;
bytes are the packed words as stored plus a bf16 activation in and out
(the model's activation type; the kernel may widen them, a later kernel
need not).
"""
from __future__ import annotations

ACT_BYTES = 2          # bf16 activations at the layer boundary


def work(m: int, k: int, n: int, bits: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one call."""
    flops = 2.0 * m * k * n
    weight_bytes = k * n * bits / 8.0
    act_bytes = (m * k + m * n) * ACT_BYTES
    return flops, weight_bytes + act_bytes
