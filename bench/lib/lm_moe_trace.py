"""What the MoE per-layer metrics read from a trace: the grouped
``codr_gmm`` kernel's calls with their shapes, the programs that hold
them, and for each such program the batcher's record of how many
(layer, expert) pairs it gave rows (``ContinuousBatcher
.experts_touched``, handed over as ``ctx.model["experts_touched"]``).

A program and its record are matched by order: the traced programs
(prefills and pooled decode steps, each named by its jit and sized by
the rows of its ``codr_matmul`` calls) are laid along the records (kind
and token rows) at the offset where every one agrees, the one whose
first record lies nearest the program's end on the host's clock when
several do.
"""
from __future__ import annotations

import bisect
import importlib
import re

from bench.lib import lm_trace, peaks

# "%codr_gmm.3 = f32[8,896,256]{..} custom-call(s32[64]{0} %a, ...,
#  f32[896,2048]{..} %x, s32[64,2048,176]{..} %w, ...": planes x rows x
# padded word columns; the packed words (E, K, N * bits / 32) are the
# first three-dimensional operand
GMM = re.compile(r"^%codr_gmm[\w.]* = \w+\[(\d+),\d+,\d+\]")
SHAPE = re.compile(r"\b[a-z]+\d+\[(\d+(?:,\d+)*)\]")


def gmm_calls(trace) -> list[tuple]:
    """``(event, k, n, bits)`` for each ``codr_gmm`` call."""
    out = []
    for e in trace.ops:
        mt = GMM.match(e.name)
        if not mt:
            continue
        planes = int(mt.group(1))
        args = e.name.partition("custom-call(")[2]
        dims = [tuple(map(int, s.split(","))) for s in SHAPE.findall(args)]
        words = next((d for d in dims if len(d) == 3), None)
        if words is None:
            continue
        out.append((e, words[1], words[2] * planes, 32 // planes))
    return out


def _inside(events, programs) -> dict:
    """Events of each program run, by the run's index; an event belongs
    to the run whose interval holds its start."""
    starts = [p.start for p in programs]
    out = {}
    for item in events:
        e = item[0]
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < programs[i].end:
            out.setdefault(i, []).append(item)
    return out


def _kind(name: str) -> str | None:
    if "decode_step" in name:
        return "step"
    if "prefill" in name:
        return "prefill"
    return None


def matched_programs(trace, records, trace_t0: float) -> list[tuple]:
    """``(calls, record)`` for each traced program that holds
    ``codr_gmm`` calls, its record being ``(host time, kind, token
    rows, experts touched)``; ``[]`` when no offset agrees."""
    programs = sorted((p for p in trace.programs if _kind(p.name)),
                      key=lambda p: p.start)
    gmm = _inside(gmm_calls(trace), programs)
    dense = _inside(lm_trace.codr_calls(trace), programs)
    runs = []
    for i in sorted(gmm):
        rows = max((m for _, m, *_ in dense.get(i, [])), default=None)
        runs.append((programs[i], gmm[i], (_kind(programs[i].name), rows)))
    if not runs or not records:
        return []
    keys = [(r[1], r[2]) for r in records]
    fits = [o for o in range(len(records) - len(runs) + 1)
            if all(keys[o + i] == run[2] for i, run in enumerate(runs))]
    if not fits:
        return []
    end0 = trace_t0 + runs[0][0].end / 1e9
    o = min(fits, key=lambda o: abs(records[o][0] - end0))
    return [(calls, records[o + i]) for i, (_, calls, _) in enumerate(runs)]


def gmm_roofline(ctx) -> float | None:
    """Least time of each matched ``codr_gmm`` call's work over the
    calls' summed device time, in percent.  A call's rows are its
    program's token rows times the experts each token picks; its
    experts given rows, the program's count over its MoE layers."""
    if ctx.trace is None:
        return None
    m = ctx.model
    pairs = matched_programs(ctx.trace, m.get("experts_touched") or [],
                             ctx.trace_t0)
    if not pairs:
        return None
    cost = importlib.import_module("bench.costs.codr_gmm")
    least = busy = 0.0
    for calls, (_, _, tokens, touched) in pairs:
        per_layer = touched / m["n_moe_layers"]
        for e, k, n, bits in calls:
            least += peaks.least_time_s(
                *cost.work(tokens * m["top_k"], k, n, bits, per_layer),
                ctx.device_kind)
            busy += e.dur / 1e9
    return 100.0 * least / busy if busy else None


def gmm_ms_per_step(trace) -> float | None:
    """``codr_gmm`` device time inside the pooled decode step's runs,
    over the number of runs, in ms."""
    runs = lm_trace.decode_runs(trace)
    if not runs:
        return None
    inside = _inside(gmm_calls(trace), runs)
    if not inside:
        return None
    total = sum(e.dur for items in inside.values() for e, *_ in items)
    return total / 1e6 / len(runs)
