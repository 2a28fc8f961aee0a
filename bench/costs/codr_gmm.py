"""Work of one ``codr_gmm`` call: ``rows`` token rows sorted by expert,
each ``(k,)`` times its expert's ``(k, n)`` matrix, stored as
``bits``-bit indices packed in uint32 words, ``touched`` experts of the
stack given rows.

Counted from the layer, not from the kernel's loops: 2*rows*k*n
operations; bytes are the packed words of the experts given rows (an
expert without rows need not be read) plus a bf16 activation in and out
per row (the model's activation type; the kernel may widen them, a
later kernel need not).
"""
from __future__ import annotations

ACT_BYTES = 2          # bf16 activations at the layer boundary


def work(rows: int, k: int, n: int, bits: int,
         touched: float) -> tuple[float, float]:
    """``(operations, bytes)`` of one call."""
    flops = 2.0 * rows * k * n
    weight_bytes = touched * k * n * bits / 8.0
    act_bytes = rows * (k + n) * ACT_BYTES
    return flops, weight_bytes + act_bytes
