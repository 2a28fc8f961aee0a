"""The program's own spans (``repro.runtime.tracing``) in the benchmark:
recorded during a traced run, put on the device trace's clock, and
reduced to what the span metrics read.

A span is stamped with ``time.monotonic_ns()``.  A profiler trace
(``.xplane.pb``) stores event times in ns from the start of its
session, and its ``Task Environment`` plane holds that start in
wall-clock ns (``profile_start_time``), with the host tracer off too.
One pair ``(monotonic_ns, time_ns)`` read together right after the
profiler starts links the two clocks:

    trace_ns = mono_ns - mono_a + real_a - profile_start_time

On a TPU that places host stamps up to ~1 ms off the device's events,
so a tiny marker program run right after the pair bounds it: its
device run lies between its dispatch and its return on the host, and
``marker_shift`` moves the mapping the least that puts it there.

Everything here works on plain lists of spans and trace events, so the
tests check it on small hand-made traces.  ``bench/spans.py`` makes the
traced runs that record the spans.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import heapq
import time
from pathlib import Path

import numpy as np

from bench.lib import lm_stats, xtrace

NO_SPAN = "(no span)"
EDGE_NS = 1e5               # 0.1 ms: slack of the clock checks


# -- clocks -------------------------------------------------------------------

def clock_pair() -> tuple[int, int]:
    """``(time.monotonic_ns(), time.time_ns())`` read back to back."""
    return time.monotonic_ns(), time.time_ns()


def profile_start_ns(path) -> int | None:
    """The trace's ``profile_start_time`` (wall-clock ns), or None;
    ``path`` is an ``.xplane.pb`` or a directory that holds one (the
    first, as ``xtrace.load_dir`` takes it)."""
    from jax.profiler import ProfileData
    if Path(path).is_dir():
        path = glob.glob(str(Path(path) / "**" / "*.xplane.pb"),
                         recursive=True)[0]
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        if plane.name == "Task Environment":
            for k, v in plane.stats:
                if k == "profile_start_time":
                    return int(v)
    return None


def offset_ns(pair: tuple[int, int], start_ns: int) -> int:
    """``trace_ns = mono_ns + offset_ns(pair, profile_start_time)``."""
    return pair[1] - pair[0] - start_ns


def marker_shift(programs, name: str, a_ns: int, b_ns: int,
                 offset: int) -> int:
    """ns to add to ``offset`` so that the device run of the marker
    program ``name``, dispatched at host time ``a_ns`` and seen done at
    ``b_ns`` (monotonic), starts after ``a_ns`` and ends before ``b_ns``
    on the trace's clock; 0 where it already does, or where the trace
    holds no run of it."""
    runs = [p for p in programs if p.name.startswith(name)]
    if not runs:
        return 0
    p = runs[0]
    return int(min(max(offset, p.end - b_ns), p.start - a_ns)) - offset


def on_trace(spans, offset: int) -> list:
    """Copies of ``spans`` with their ends on the trace's clock."""
    return [dataclasses.replace(s, start_ns=s.start_ns + offset,
                                end_ns=s.end_ns + offset) for s in spans]


# -- device busy time ---------------------------------------------------------

class Busy:
    """The device's busy union, asked for its busy ns in many intervals
    at once: ``xtrace.busy_within`` scans every op per interval, too slow
    for the ~10^4 spans and ~10^5 ops of a CNN stretch."""

    def __init__(self, ops):
        iv = sorted((e.start, e.end) for e in ops)
        s, e = [], []
        for a, b in iv:
            if e and a <= e[-1]:
                e[-1] = max(e[-1], b)
            else:
                s.append(a)
                e.append(b)
        self.s, self.e = np.asarray(s, np.float64), np.asarray(e, np.float64)
        self.pre = np.concatenate([[0.0], np.cumsum(self.e - self.s)])

    def before(self, t) -> np.ndarray:
        """Busy ns before each time in ``t``."""
        t = np.asarray(t, np.float64)
        if not len(self.s):
            return np.zeros_like(t)
        i = np.searchsorted(self.s, t, side="right") - 1
        j = np.maximum(i, 0)
        part = np.minimum(t, self.e[j]) - self.s[j]
        return np.where(i >= 0, self.pre[j] + part, 0.0)

    def within(self, lo, hi) -> np.ndarray:
        return self.before(hi) - self.before(lo)


# -- spans on the trace -------------------------------------------------------

@dataclasses.dataclass
class SpanTrace(xtrace.Trace):
    """A trace with the program's spans on its clock, and the traced
    stretch there."""
    spans: list = dataclasses.field(default_factory=list)
    offset_ns: int = 0          # trace ns = monotonic ns + offset_ns
    lo_ns: float = 0.0          # the traced stretch, trace clock
    hi_ns: float = 0.0

    def breakdown(self, top: int = 10) -> dict:
        out = super().breakdown(top)
        out["idle_by_span"] = [[k, v] for k, v in idle_by_span(self, top)]
        return out


def attach(trace: xtrace.Trace, spans, offset: int, t0: float,
           t1: float) -> SpanTrace:
    """``trace`` with ``spans`` (program clock) mapped onto its clock by
    ``offset`` ns, and the traced stretch ``[t0, t1]`` (host monotonic
    seconds) there."""
    base = {f.name: getattr(trace, f.name) for f in dataclasses.fields(trace)}
    return SpanTrace(**base, spans=on_trace(spans, offset), offset_ns=offset,
                     lo_ns=t0 * 1e9 + offset, hi_ns=t1 * 1e9 + offset)


def depths(spans) -> dict:
    """Each span's depth: how many of its ancestors are among ``spans``."""
    by_id = {s.id: s for s in spans}
    out = {}
    for s in spans:
        d, p = 0, s.parent
        while p in by_id:
            d, p = d + 1, by_id[p].parent
        out[s.id] = d
    return out


def innermost(spans) -> list[tuple[float, float, str]]:
    """``(start, end, name)`` pieces of time, each under the innermost
    span open then: the deepest, the later start on a tie.  Spans that
    start and end on different threads (``thread`` None: a request in a
    queue) are left out; they wait, and do no work."""
    own = [s for s in spans if s.thread is not None]
    depth = depths(own)
    events = sorted([(s.start_ns, 1, i) for i, s in enumerate(own)]
                    + [(s.end_ns, 0, i) for i, s in enumerate(own)])
    heap, closed, out, prev = [], set(), [], None
    for t, opens, i in events:
        while heap and heap[0][2] in closed:
            heapq.heappop(heap)
        if heap and t > prev:
            out.append((prev, t, own[heap[0][2]].name))
        prev = t
        if opens:
            s = own[i]
            heapq.heappush(heap, (-depth[s.id], -s.start_ns, i))
        else:
            closed.add(i)
    return out


def idle_by_span(trace: SpanTrace, top: int = 10) -> list[tuple[str, float]]:
    """The device's idle seconds in the traced stretch by the innermost
    program span open over them; idle time under no span is
    ``(no span)``.  The seconds sum to the stretch's idle time."""
    lo, hi = trace.lo_ns, trace.hi_ns
    if not trace.ops or hi <= lo:
        return []
    busy = Busy(trace.ops)
    segs = [(max(a, lo), min(b, hi), n) for a, b, n in innermost(trace.spans)
            if b > lo and a < hi]
    out = collections.Counter()
    if segs:
        a = np.array([s[0] for s in segs])
        b = np.array([s[1] for s in segs])
        idle = (b - a) - busy.within(a, b)
        for (_, _, name), v in zip(segs, idle):
            out[name] += float(v) / 1e9
    total = float((hi - lo) - busy.within(lo, hi)) / 1e9
    out[NO_SPAN] += total - sum(out.values())
    return out.most_common(top)


def idle_while(trace: SpanTrace, name: str) -> float:
    """Idle ns of the device in the traced stretch while a ``name`` span
    is open (spans of one name on one thread never overlap)."""
    lo, hi = trace.lo_ns, trace.hi_ns
    iv = [(max(s.start_ns, lo), min(s.end_ns, hi)) for s in trace.spans
          if s.name == name and s.end_ns > lo and s.start_ns < hi]
    if not iv:
        return 0.0
    a, b = np.array(iv).T
    return float(((b - a) - Busy(trace.ops).within(a, b)).sum())


# -- what the readers take ----------------------------------------------------

def in_window(ctx, name: str, by: str = "start") -> list:
    """Spans named ``name`` whose ``by`` end (``start`` or ``end``) lies
    in the window (program clock); [] where the run recorded none."""
    got = getattr(ctx, "spans", None) or []
    lo, hi = ctx.t0 * 1e9, ctx.t1 * 1e9
    return [s for s in got if s.name == name
            and lo <= getattr(s, f"{by}_ns") <= hi]


# -- checks of the clock and of the time-to-first-token split ----------------

def runs_inside(programs, spans, slack: float = EDGE_NS) -> tuple[int, int]:
    """How many of the program runs lie inside one of ``spans`` (within
    ``slack`` ns at either end), of how many."""
    spans = sorted(spans, key=lambda s: s.start_ns)
    starts = [s.start_ns for s in spans]
    inside = 0
    for p in programs:
        i = bisect.bisect_right(starts, p.start + slack) - 1
        inside += i >= 0 and p.end <= spans[i].end_ns + slack
    return inside, len(programs)


def runs_after(programs, spans) -> tuple[int, int, list]:
    """How many program runs start after the span that last opened
    before them had closed, of how many, and each such run's start less
    that span's end (ns; negative where it starts first)."""
    spans = sorted(spans, key=lambda s: s.start_ns)
    starts = [s.start_ns for s in spans]
    lags = []
    for p in programs:
        i = bisect.bisect_right(starts, p.start) - 1
        if i >= 0:
            lags.append(p.start - spans[i].end_ns)
    return sum(g >= 0 for g in lags), len(programs), lags


def clock_checks(trace: SpanTrace) -> dict:
    """The spans placed against the device's program runs: every run of
    the decode step inside a ``batcher.step``; every run of the CNN
    chain after its call's ``model.put``, with the 1st, 50th and 99th
    percentiles of how long after the put's end each run starts."""
    out = {}
    for prog, span, check in (("jit_decode_step", "batcher.step",
                               runs_inside),
                              ("jit_cnn_chain", "model.put", runs_after)):
        runs = [p for p in trace.programs if p.name.startswith(prog)
                and trace.lo_ns <= p.start <= trace.hi_ns]
        sp = [s for s in trace.spans if s.name == span]
        if runs and sp:
            ok, n, *lags = check(runs, sp)
            got = out[f"{prog} vs {span}"] = {
                "ok": ok, "of": n, "share": ok / n,
                "spans": sum(trace.lo_ns <= s.start_ns <= trace.hi_ns
                             for s in sp)}
            if lags and lags[0]:
                got["lag_ms"] = [float(np.percentile(lags[0], q)) / 1e6
                                 for q in (1, 50, 99)]
    return out


def durations_ms(ctx) -> dict:
    """Count, median and 95th percentile (ms) of each span name's
    durations, over the spans that start inside the window."""
    by = collections.defaultdict(list)
    for s in ctx.spans:
        if ctx.t0 * 1e9 <= s.start_ns <= ctx.t1 * 1e9:
            by[s.name].append(s.dur_ns / 1e9)
    return {k: {"n": len(v), "p50": lm_stats.percentile_ms(v, 50),
                "p95": lm_stats.percentile_ms(v, 95)}
            for k, v in sorted(by.items())}


def ttft_split(ctx) -> dict | None:
    """For each request due in the window: queue wait + prefill (program
    spans) against its time to first token at the client; what remains
    is the generator's lateness and delivery."""
    q = sorted((s for s in ctx.spans if s.name == "batcher.queue"),
               key=lambda s: s.start_ns)
    pre = {s.rid: s for s in ctx.spans if s.name == "batcher.prefill"}
    starts = [s.start_ns for s in q]
    rows, due = [], 0
    for r in ctx.requests:
        due += ctx.t0 <= r.due < ctx.t1
        if not (ctx.t0 <= r.due < ctx.t1 and r.times and r.submitted):
            continue
        # the submit stamps its queue span right after the client's stamp
        i = bisect.bisect_left(starts, r.submitted * 1e9)
        if i == len(q) or q[i].attrs.get("prompt_len") != r.prompt_len \
                or q[i].rid not in pre:
            continue
        ttft = r.times[0] - r.due
        work = (q[i].dur_ns + pre[q[i].rid].dur_ns) / 1e9
        rows.append((ttft, work, q[i].dur_ns / 1e9,
                     pre[q[i].rid].dur_ns / 1e9))
    if not rows:
        return None
    ttft, work, qw, pf = (np.array(c) for c in zip(*rows))
    rest = ttft - work
    pct = lm_stats.percentile_ms
    return {"due": due, "requests": len(rows),
            "within_ttft": int(np.count_nonzero(rest >= 0)),
            "remainder_p50_ms": pct(rest, 50),
            "remainder_p95_ms": pct(rest, 95),
            "queue_p50_ms": pct(qw, 50), "queue_p95_ms": pct(qw, 95),
            "prefill_p50_ms": pct(pf, 50), "prefill_p95_ms": pct(pf, 95),
            "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95)}
