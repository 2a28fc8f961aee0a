"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: the device's busy union and idle share, and the
device's idle time between consecutive runs of one program.  Kernel
times are summed by ``lm_trace`` and ``cnn_trace``, which find their
kernels by name and shape.

``load`` turns the trace into plain ``Event`` lists (device ops, device
program runs, host events); every reduction below works on those lists,
so the tests check it on a small recorded trace.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re
from pathlib import Path

import numpy as np


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns, on the trace's clock
    dur: float              # ns
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: list               # device ops, every device, time-ordered
    programs: list          # device program (XLA module) runs
    host: list              # host events, every thread
    n_devices: int
    window_s: float         # length of the traced stretch

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return busy_union(self.ops) / 1e9 / max(self.n_devices, 1)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (loops that hold other ops
        left out), and the idle time by what the host was doing."""
        by_op = collections.Counter()
        for e in self.ops:
            if not CONTAINER.match(e.name):
                by_op[op_label(e)] += e.dur / 1e9
        return {"device_ops": [[k, v] for k, v in by_op.most_common(top)],
                "idle_gaps": [[k, v] for k, v in
                              idle_by_host(self, top=top)]}


# ops that only hold other ops (a scanned layer stack's loop)
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* ")


def op_label(e: Event) -> str:
    """A device op's label: its HLO name and result type, without
    layouts (a TPU trace names each op by its whole instruction)."""
    name = re.sub(r"\{[^{}]*\}", "", e.name)
    return name.split("(")[0].strip()[:120]


def busy_union(events) -> float:
    """Total length of the union of the events' intervals."""
    iv = sorted((e.start, e.end) for e in events)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in iv:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(events) -> list[tuple[float, float]]:
    """Intervals between the union's pieces, in time order."""
    iv = sorted((e.start, e.end) for e in events)
    gaps, cur_e = [], None
    for s, e in iv:
        if cur_e is not None and s > cur_e:
            gaps.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return gaps


def busy_within(events, lo: float, hi: float) -> float:
    """Busy time of ``events`` clipped to ``[lo, hi)``."""
    clipped = [Event("", max(e.start, lo), min(e.end, hi) - max(e.start, lo))
               for e in events if e.end > lo and e.start < hi]
    return busy_union(clipped)


def start_to_start_idle(trace: Trace, runs) -> tuple[float, float]:
    """Over each interval from one run's start to the next run's start:
    ``(idle ns, total ns)`` summed."""
    idle = total = 0.0
    for a, b in zip(runs, runs[1:]):
        span = b.start - a.start
        idle += span - busy_within(trace.ops, a.start, b.start)
        total += span
    return idle, total


def idle_by_host(trace: Trace, top: int = 10, min_gap_ns: float = 1e5,
                 max_gaps: int = 4000) -> list[tuple[str, float]]:
    """Device idle seconds by what the host was doing: each gap of at
    least ``min_gap_ns`` (the longest ``max_gaps``) goes to the host
    event that overlaps it most, the shortest such on a tie, so a
    thread's whole span does not take every gap; shorter gaps are
    summed apart."""
    gaps = idle_gaps(trace.ops)
    out = collections.Counter()
    short = sum(hi - lo for lo, hi in gaps if hi - lo < min_gap_ns)
    long_ = sorted((g for g in gaps if g[1] - g[0] >= min_gap_ns),
                   key=lambda g: g[0] - g[1])
    if trace.host:
        st = np.array([e.start for e in trace.host])
        en = np.array([e.end for e in trace.host])
        du = en - st
    for i, (lo, hi) in enumerate(long_):
        name = "(no host event)"
        if trace.host and i < max_gaps:
            ov = np.minimum(en, hi) - np.maximum(st, lo)
            if ov.max() > 0:
                best = np.lexsort((du, -ov))[0]
                name = trace.host[int(best)].name
        elif i >= max_gaps:
            name = "(shorter gaps, not attributed)"
        out[name] += (hi - lo) / 1e9
    if short:
        out[f"(gaps under {min_gap_ns / 1e6:g} ms)"] += short / 1e9
    return out.most_common(top)


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        out[str(k)] = v
    return out


def load(path: str | Path, window_s: float) -> Trace:
    """Read one ``.xplane.pb``: device planes' op and module lines, and
    every host thread's events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, programs, host, n_dev = [], [], [], 0
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Event(e.name, e.start_ns, e.duration_ns,
                                     _stats(e)) for e in line.events)
                elif line.name == "XLA Modules":
                    programs.extend(Event(e.name, e.start_ns,
                                          e.duration_ns, _stats(e))
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0)

    ops.sort(key=lambda e: e.start)
    return Trace(ops=ops, programs=programs, host=host, n_devices=n_dev,
                 window_s=window_s)


def load_dir(directory: str | Path, window_s: float) -> Trace:
    files = glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return load(files[0], window_s)
