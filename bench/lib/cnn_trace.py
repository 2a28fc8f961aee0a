"""What the CNN per-layer metrics read from a trace: the convolution
ops of the chain, found by their weight operand's shape."""
from __future__ import annotations

import importlib
import re

from bench.lib import peaks

OP = re.compile(r"^%(\S+) = .*? (fusion|convolution)\((.*)$")


def conv_ops(trace, convs) -> list:
    """Device ops that convolve with one of ``convs``' weights
    ``[cout, cin, k, k]`` (a layout copy of the weights is no conv)."""
    shapes = [f"[{co},{ci},{k},{k}]" for _, ci, co, k in convs]
    out = []
    for e in trace.ops:
        m = OP.match(e.name)
        if m and any(s in m.group(3) for s in shapes):
            out.append(e)
    return out


def chain_work(convs, batch: int) -> tuple[float, float]:
    """Operations of every convolution; bytes of the input map, the
    weights and the final map, in float32."""
    cost = importlib.import_module("bench.costs.conv")
    flops = sum(cost.work(batch, hw, hw, ci, co, k, k)[0]
                for hw, ci, co, k in convs)
    hw0, ci0 = convs[0][0], convs[0][1]
    hw, co, k = convs[-1][0], convs[-1][2], convs[-1][3]
    ho = hw - k + 1
    nbytes = cost.F32 * (batch * hw0 * hw0 * ci0 + batch * ho * ho * co
                         + sum(c * i * kk * kk for _, i, c, kk in convs))
    return flops, nbytes


def conv_roofline(ctx) -> float | None:
    convs = ctx.model["convs"]
    ops = conv_ops(ctx.trace, convs)
    if not ops:
        return None
    n_calls = len(ops) / len(convs)
    least = peaks.least_time_s(*chain_work(convs, ctx.mix["batch"]),
                               ctx.device_kind)
    return 100.0 * least * n_calls / (sum(e.dur for e in ops) / 1e9)
