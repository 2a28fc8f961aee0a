"""What the LM per-layer metrics read from a trace: the ``codr_matmul``
kernel's calls with their shapes, and the runs of the pooled decode
step."""
from __future__ import annotations

import collections
import importlib
import re

from bench.lib import peaks, xtrace

# "%codr_matmul_pallas.67 = f32[8,32,1376]{..} custom-call(f32[32,2048]{..}
#  %x, s32[2048,1376]{..} %w, ...": planes x rows x word columns, then the
# activations (M, K) and the packed words (K, N * bits / 32)
CODR = re.compile(r"^%codr_matmul[\w.]* = \w+\[(\d+),(\d+),(\d+)\]\S* "
                  r"custom-call\(\w+\[(\d+),(\d+)\]\S* \S+, \w+\[(\d+),"
                  r"(\d+)\]")


def codr_calls(trace) -> list[tuple]:
    """``(event, m, k, n, bits)`` for each ``codr_matmul`` call."""
    out = []
    for e in trace.ops:
        mt = CODR.match(e.name)
        if mt:
            planes, m, words, _, k, _, _ = map(int, mt.groups())
            out.append((e, m, k, words * planes, 32 // planes))
    return out


def codr_roofline(trace, device_kind: str) -> float | None:
    """Least time of every call's work over the calls' summed device
    time, in percent."""
    calls = codr_calls(trace)
    if not calls:
        return None
    cost = importlib.import_module("bench.costs.codr_matmul")
    least = sum(peaks.least_time_s(*cost.work(m, k, n, b), device_kind)
                for _, m, k, n, b in calls)
    return 100.0 * least / (sum(e.dur for e, *_ in calls) / 1e9)


def decode_runs(trace) -> list:
    """Runs of the pooled decode step: of the programs that call the
    kernel, the one run most often (each prompt length has a prefill
    program of its own; the decode step is one program)."""
    kernels = sorted(e.start for e, *_ in codr_calls(trace))
    if not kernels:
        return []
    import bisect
    runs = collections.defaultdict(list)
    for p in trace.programs:
        i = bisect.bisect_left(kernels, p.start)
        if i < len(kernels) and kernels[i] < p.end:
            runs[p.name].append(p)
    if not runs:
        return []
    name = max(runs, key=lambda k: len(runs[k]))
    return sorted(runs[name], key=lambda e: e.start)


def decode_gap_share(trace) -> float | None:
    runs = decode_runs(trace)
    if len(runs) < 2:
        return None
    idle, total = xtrace.start_to_start_idle(trace, runs)
    return 100.0 * idle / total
