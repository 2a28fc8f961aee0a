"""Work of one VALID, stride-1 NHWC convolution with float32 operands,
from the layer's shapes: 2*B*Ho*Wo*Cout*Cin*kh*kw operations; bytes are
the input map, the weights and the output map, each read or written
once in float32.
"""
from __future__ import annotations

F32 = 4


def work(batch: int, h: int, w: int, c_in: int, c_out: int, kh: int,
         kw: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one convolution call."""
    ho, wo = h - kh + 1, w - kw + 1
    flops = 2.0 * batch * ho * wo * c_out * c_in * kh * kw
    nbytes = F32 * (batch * h * w * c_in + c_out * c_in * kh * kw
                    + batch * ho * wo * c_out)
    return flops, nbytes
