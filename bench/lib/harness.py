"""One run of one cell: set-up, the measured window, the correctness
check, and the metrics, all found by name.

A cell's configuration file names its runner (``bench/runners/<runner>
.py``); its traffic names a mix (``bench/traffic/<traffic>.json``); each
metric is read by ``bench/metrics/<metric>.py``.  Nothing here branches
on a cell's name.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax

from bench.lib import traffic as traffic_lib

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
TRACE_SECONDS = 10.0       # traced stretch after a --trace 1 window
# The profiler records device ops and the host's own events, but no
# Python function calls: tracing those slows a host-bound loop many
# times over, and the traced stretch would then describe the profiler.
# A runner whose loop the host events slow too sets its own
# ``PROFILE_OPTIONS``.
PROFILE_OPTIONS = {"host_tracer_level": 1, "python_tracer_level": 0}


class CompileClock:
    """Counts and times JAX's tracing, lowering and compile events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.events += 1
            self.programs += event == self.EVENTS[-1]


@dataclasses.dataclass
class RequestRecord:
    """One served request as its client saw it (host monotonic clock)."""
    due: float                      # when it was due to be sent
    prompt_len: int
    max_new_tokens: int
    submitted: float | None = None
    times: list = dataclasses.field(default_factory=list)   # token arrivals
    tokens: list = dataclasses.field(default_factory=list)
    logits: list = dataclasses.field(default_factory=list)  # served rows
    status: str = "pending"         # done | failed | cancelled | pending


@dataclasses.dataclass
class CallRecord:
    """One closed-loop call: ``items`` results ready at ``end``."""
    start: float
    end: float
    items: int


@dataclasses.dataclass
class Outcome:
    """What a runner hands back."""
    correct: bool
    attempted: int
    failed: int
    checks: list                    # [(name, value, limit)]
    memory_peak_bytes: int
    requests: list = dataclasses.field(default_factory=list)
    calls: list = dataclasses.field(default_factory=list)
    model: dict = dataclasses.field(default_factory=dict)
    wait_end: float | None = None   # last moment late answers were awaited
    # (correct, checks) of the control put in the program's place and
    # judged by the same comparison; only when ``Run.control`` is set
    control: tuple | None = None


@dataclasses.dataclass
class Ctx:
    """Everything a metric reader may look at."""
    cell: dict
    config: dict
    mix: dict
    seconds: float
    device_kind: str
    setup_s: float
    t0: float                       # window, host monotonic clock
    t1: float
    requests: list
    calls: list
    model: dict
    trace: object = None            # bench.lib.xtrace.Trace or None
    trace_t0: float | None = None   # traced stretch, host clock
    trace_t1: float | None = None
    wait_end: float | None = None


class Run:
    """The run as a runner sees it: inputs, and the window's clock."""

    def __init__(self, *, config: dict, mix: dict, seed: int,
                 seconds: float, trace: bool, t_start: float,
                 clock: CompileClock):
        self.config, self.mix, self.seed = config, mix, seed
        self.seconds, self.trace = seconds, trace
        self.t_start, self.clock = t_start, clock
        self.setup_s = None
        self.t0 = self.t1 = None
        self.end = None
        self.trace_dir = None
        self.profile_options = PROFILE_OPTIONS
        self.trace_t0 = self.trace_t1 = None
        self._compile_events_at_open = 0
        self.compiles_in_window = None
        self.wait_end = None
        # also judge the control (the reference one precision lower) in
        # the program's place; bench/control.py sets it, the benchmark's
        # own runs never do
        self.control = False

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def end_setup(self) -> None:
        """Set-up ends here: the traffic starts next."""
        self.setup_s = time.monotonic() - self.t_start
        self.log(f"setup_s {self.setup_s!r}; of it compile "
                 f"{self.clock.seconds!r} s over {self.clock.programs} "
                 f"backend compiles ({self.clock.events} trace/lower/"
                 f"compile events)")

    def open_window(self, at: float | None = None) -> float:
        """The measured window opens (now, or at host time ``at``).  With
        ``trace``, the traffic runs on past its close for a traced
        stretch, so the window itself runs without the profiler."""
        if self.setup_s is None:
            self.end_setup()
        self.t0 = time.monotonic() if at is None else at
        self.t1 = self.t0 + self.seconds
        self.end = self.t1 + (TRACE_SECONDS if self.trace else 0.0)
        self._compile_events_at_open = self.clock.events
        return self.t0

    def tick(self, now: float | None = None) -> None:
        """Start the profiler at the window's close, stop it at ``end``."""
        if not self.trace:
            return
        now = time.monotonic() if now is None else now
        if self.trace_t0 is None and now >= self.t1:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            for k, v in self.profile_options.items():
                setattr(opts, k, v)
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.trace_t0 = time.monotonic()
        elif self.trace_t1 is None and self.trace_t0 is not None \
                and now >= self.end:
            self._stop_trace()

    def _stop_trace(self) -> None:
        self.trace_t1 = time.monotonic()
        jax.profiler.stop_trace()

    def sleep_until(self, t: float) -> None:
        """Sleep to host time ``t``, starting and stopping the profiler
        on time."""
        while True:
            self.tick()
            now = time.monotonic()
            if now >= t:
                return
            nxt = [t] + [x for x in (self.t1, self.end)
                         if self.trace and x is not None and x > now]
            time.sleep(max(min(nxt) - now, 0.0))

    def close_window(self) -> None:
        """The traffic has stopped (window and traced stretch)."""
        self.tick()
        if self.trace_t0 is not None and self.trace_t1 is None:
            self._stop_trace()
        n = self.clock.events - self._compile_events_at_open
        self.compiles_in_window = n
        self.log(f"compilations inside the window: {n}")


def peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the
    backend keeps no such count)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(spec: dict, cell: str, group: str) -> list[dict]:
    """The cell's metrics of ``group`` (``end_to_end`` or ``per_layer``):
    those that list it, or list no cells and move a metric it reports."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def read_metric(name: str, ctx: Ctx):
    mod = load_module(BENCH_DIR / "metrics" / f"{name}.py",
                      f"bench_metric_{name.replace('.', '_')}")
    return mod.read(ctx)


def run_cell(spec: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, clock: CompileClock,
             config: dict | None = None, mix: dict | None = None,
             control: bool = False) -> dict:
    """Run ``cell`` once and return the result line's object.
    ``config`` and ``mix`` stand in for the files the cell names (the
    tests run cells at small sizes so)."""
    if config is None:
        conf = next(c for c in spec["configs"]
                    if c["name"] == cell["config"])
        with open(ROOT / conf["file"]) as f:
            config = json.load(f)
    if mix is None:
        mix = traffic_lib.load(cell["traffic"])
    runner = importlib.import_module(f"bench.runners.{config['runner']}")
    run = Run(config=config, mix=mix, seed=seed, seconds=seconds,
              trace=trace, t_start=t_start, clock=clock)
    run.control = control
    run.profile_options = getattr(runner, "PROFILE_OPTIONS", PROFILE_OPTIONS)
    out = runner.run(run)
    devices = jax.devices()
    kind = devices[0].device_kind
    tr = None
    if trace and run.trace_dir:
        from bench.lib import xtrace
        try:
            tr = xtrace.load_dir(run.trace_dir,
                                 run.trace_t1 - run.trace_t0)
        finally:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
    ctx = Ctx(cell=cell, config=config, mix=mix, seconds=seconds,
              device_kind=kind, setup_s=run.setup_s, t0=run.t0, t1=run.t1,
              requests=out.requests, calls=out.calls, model=out.model,
              trace=tr, trace_t0=run.trace_t0, trace_t1=run.trace_t1,
              wait_end=out.wait_end)
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(spec, cell["name"], group):
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": int(out.memory_peak_bytes)}
    result = {"correct": bool(out.correct), "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": metrics,
              "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
    run.log(f"compiles_in_window {run.compiles_in_window}")
    if tr is not None:
        run.log(f"work per second, window {_rate(out, run.t0, run.t1)!r}; "
                f"traced stretch {_rate(out, run.trace_t0, run.trace_t1)!r}")
    for name, value, limit in out.checks:
        print(f"check {name}: {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    if out.control is not None:
        ok, checks = out.control
        result["control"] = {"correct": bool(ok), "checks": _checks(checks)}
    result["checks"] = _checks(out.checks)
    return result


def _rate(out: Outcome, lo: float, hi: float) -> float:
    """Items of closed-loop calls, or tokens of requests, that were
    ready in ``[lo, hi]``, per second."""
    n = sum(c.items for c in out.calls if lo <= c.end <= hi)
    n += sum(1 for r in out.requests for t in r.times if lo <= t <= hi)
    return n / (hi - lo)


def _checks(checks) -> dict:
    return {name: {"value": value, "limit": limit}
            for name, value, limit in checks}
