"""The program's spans in the benchmark (``bench/lib/spans.py``): the
clock that puts them on a real profiler trace, the idle time by span and
the span metrics on a small hand-made trace whose answers are known, the
existing reduction unchanged on the recorded trace, and whole traced
runs of small cells on the CPU."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import spans as bench_spans
from bench.lib import harness, spans, xtrace
from bench.lib.harness import RequestRecord
from bench.lib.xtrace import Event
from bench.tests import tiny
from repro.runtime import tracing
from repro.runtime.tracing import Span

DATA = Path(__file__).parent / "data"
MS = 1e6
S = 1e9


def _reader(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py",
                               f"span_reader_{name.replace('.', '_')}")


@pytest.fixture
def recorder():
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()


def _profile(tmp_path, level, body):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = level, 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        pair = spans.clock_pair()
        body()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    return pair, path


def test_clock_maps_a_span_onto_its_annotation(tmp_path, recorder):
    import jax

    def body():
        tracing.enable()
        with tracing.span("probe"), jax.profiler.TraceAnnotation("probe"):
            time.sleep(0.02)
        tracing.disable()
    pair, path = _profile(tmp_path, 1, body)
    (probe,) = tracing.drain()
    start = spans.profile_start_ns(path)
    (mapped,) = spans.on_trace([probe], spans.offset_ns(pair, start))
    (ann,) = [e for e in xtrace.load(path, 1.0).host if e.name == "probe"]
    assert abs(mapped.start_ns - ann.start) < 1 * MS
    assert abs(mapped.end_ns - ann.end) < 1 * MS


@pytest.mark.parametrize("offset,shift", [
    (0, 0),                 # the marker already lies between the stamps
    (1 * MS, -0.5 * MS),    # spans mapped late: the marker started first
    (-2 * MS, 1 * MS),      # spans mapped early: it ended after the return
])
def test_marker_bounds_the_mapping(offset, shift):
    # dispatched at host 10 ms, returned at 13 ms; ran 10.5-12 ms
    runs = [Event("jit_decode_step(1)", 0, 1 * MS),
            Event("jit_clock_marker(2)", 10.5 * MS, 1.5 * MS)]
    got = spans.marker_shift(runs, "jit_clock_marker", int(10 * MS),
                             int(13 * MS), int(offset))
    assert got == pytest.approx(shift)
    assert spans.marker_shift(runs[:1], "jit_clock_marker", 0, 1,
                              int(offset)) == 0


def test_profile_start_time_is_there_without_host_events(tmp_path):
    pair, path = _profile(tmp_path, 0, lambda: time.sleep(0.01))
    start = spans.profile_start_ns(path)
    # the session started a little before the pair was read
    assert 0 <= pair[1] - start < 10 * S
    assert xtrace.load(path, 1.0).host == []


def _span(name, a, b, id_, parent=None, thread=1, rid=None, **attrs):
    return Span(name, int(a), int(b), id_, parent, rid, attrs, thread)


def small_trace():
    """Device busy [0,2) [5,6) [9,10) ms of a 12 ms stretch; a put, a
    dispatch, a step with a fetch under it, and a queue span across the
    whole stretch recorded from two threads."""
    ops = [Event("%fusion.1 = f32[2] fusion()", 0, 2 * MS),
           Event("%fusion.2 = f32[2] fusion()", 5 * MS, 1 * MS),
           Event("%copy.3 = f32[2] copy()", 9 * MS, 1 * MS)]
    programs = [Event("jit_cnn_chain(7)", 3 * MS, 3 * MS),
                Event("jit_cnn_chain(7)", 5 * MS, 1 * MS),
                Event("jit_decode_step(3)", 6.5 * MS, 3 * MS),
                Event("jit_decode_step(3)", 11.5 * MS, 0.5 * MS)]
    sp = [_span("model.put", 2 * MS, 4 * MS, 1),
          _span("model.dispatch", 4 * MS, 5.5 * MS, 2),
          _span("batcher.step", 6 * MS, 11 * MS, 3, n_active=2),
          _span("batcher.fetch", 7 * MS, 8 * MS, 4, parent=3),
          _span("batcher.queue", 0, 12 * MS, 5, thread=None, rid=0)]
    return spans.SpanTrace(ops=ops, programs=programs, host=[], n_devices=1,
                           window_s=0.012, spans=sp, lo_ns=0.0,
                           hi_ns=12 * MS)


def test_innermost_pieces_leave_out_spans_across_threads():
    assert spans.innermost(small_trace().spans) == [
        (2 * MS, 4 * MS, "model.put"), (4 * MS, 5.5 * MS, "model.dispatch"),
        (6 * MS, 7 * MS, "batcher.step"), (7 * MS, 8 * MS, "batcher.fetch"),
        (8 * MS, 11 * MS, "batcher.step")]


def test_idle_goes_to_the_innermost_span():
    t = small_trace()
    got = dict(spans.idle_by_span(t))
    assert got == pytest.approx({"model.put": 0.002, "batcher.step": 0.003,
                                 "model.dispatch": 0.001,
                                 "batcher.fetch": 0.001, "(no span)": 0.001})
    # every idle second of the stretch is named once
    assert sum(got.values()) == pytest.approx(t.window_s - t.busy_s)
    assert t.breakdown()["idle_by_span"][0] == ["batcher.step",
                                                pytest.approx(0.003)]
    assert spans.idle_while(t, "model.put") == pytest.approx(2 * MS)


def test_busy_index_matches_the_clipped_union():
    t = small_trace()
    busy = spans.Busy(t.ops)
    for lo, hi in [(0, 12), (1, 5.5), (3, 4), (5.5, 9.5), (-1, 20)]:
        assert float(busy.within(lo * MS, hi * MS)) == pytest.approx(
            xtrace.busy_within(t.ops, lo * MS, hi * MS))


def test_clock_checks_place_program_runs_against_spans():
    got = spans.clock_checks(small_trace())
    assert got["jit_decode_step vs batcher.step"] == {
        "ok": 1, "of": 2, "share": 0.5, "spans": 1}
    # the run at 3 ms starts before the put that opened at 2 ms closed
    after = got.pop("jit_cnn_chain vs model.put")
    assert after.pop("lag_ms") == pytest.approx(
        [-1 + 0.01 * 2, -1 + 0.5 * 2, -1 + 0.99 * 2])   # runs at -1, +1 ms
    assert after == {"ok": 1, "of": 2, "share": 0.5, "spans": 1}


def test_put_idle_share_reader():
    ctx = harness.Ctx(cell={}, config={}, mix={}, seconds=51.0,
                      device_kind="TPU v5 lite", setup_s=1.0, t0=0.0,
                      t1=51.0, requests=[], calls=[], model={},
                      trace=small_trace())
    assert _reader("put_idle_share.cnn").read(ctx) == \
        pytest.approx(100 * 2 / 12)


def lm_ctx():
    """Spans on the program clock, the window 1-2 s."""
    sp = []
    for i in range(10):         # queue waits 10..100 ms, starting inside
        a = 1.1 * S + i * 50 * MS
        sp.append(_span("batcher.queue", a, a + (i + 1) * 10 * MS, 100 + i,
                        thread=None, rid=i, prompt_len=8))
    sp.append(_span("batcher.queue", 0.5 * S, 0.9 * S, 99, thread=None))
    sp += [_span("batcher.prefill", 1.2 * S, 1.23 * S, 200, rid=0),
           _span("batcher.prefill", 1.9 * S, 1.95 * S, 201, rid=1),
           _span("batcher.prefill", 1.99 * S, 2.5 * S, 202, rid=2)]
    sp += [_span("batcher.step", 1.3 * S, 1.4 * S, 300, n_active=3),
           _span("batcher.wait", 1.31 * S, 1.39 * S, 301, parent=300),
           _span("batcher.step", 1.5 * S, 1.55 * S, 302, n_active=5),
           _span("batcher.wait", 1.502 * S, 1.547 * S, 304, parent=302),
           _span("batcher.step", 2.5 * S, 2.6 * S, 305, n_active=9)]
    ctx = bench_spans.SpanCtx(cell={}, config={}, mix={}, seconds=1.0,
                              device_kind="TPU v5 lite", setup_s=1.0,
                              t0=1.0, t1=2.0, requests=[], calls=[],
                              model={}, spans=sp)
    return ctx


@pytest.mark.parametrize("metric,want", [
    ("queue_wait_p95_ms.lm_chat", 95.5),     # numpy's 95th of 10..100
    ("prefill_p95_ms.lm_chat", 30 + 0.95 * 20),
    ("step_host_ms_p50.lm_chat", (20 + 5) / 2),
    ("decode_batch_mean.lm_chat", 4.0)])
def test_lm_span_readers(metric, want):
    assert _reader(metric).read(lm_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["queue_wait_p95_ms.lm_chat",
                                    "prefill_p95_ms.lm_chat",
                                    "step_host_ms_p50.lm_chat",
                                    "decode_batch_mean.lm_chat",
                                    "put_idle_share.cnn"])
def test_span_readers_read_nothing_without_spans(metric):
    ctx = harness.Ctx(cell={}, config={}, mix={}, seconds=1.0,
                      device_kind="TPU v5 lite", setup_s=1.0, t0=1.0,
                      t1=2.0, requests=[], calls=[], model={},
                      trace=xtrace.Trace(ops=[], programs=[], host=[],
                                         n_devices=1, window_s=1.0))
    assert _reader(metric).read(ctx) is None


def test_durations_by_span_name():
    got = spans.durations_ms(lm_ctx())
    assert got["batcher.queue"] == {"n": 10, "p50": pytest.approx(55.0),
                                    "p95": pytest.approx(95.5)}
    assert got["batcher.step"]["n"] == 2      # the third starts after t1


def test_ttft_split():
    ctx = lm_ctx()
    # request 0 submitted just before its queue span (1.1 s), due at
    # 1.05 s, first token at 1.3 s: 250 ms, of which queue 10 + prefill 30
    ctx.requests = [RequestRecord(due=1.05, prompt_len=8, max_new_tokens=4,
                                  submitted=1.1 - 1e-6, times=[1.3, 1.4]),
                    RequestRecord(due=1.5, prompt_len=8, max_new_tokens=4,
                                  submitted=None)]
    got = spans.ttft_split(ctx)
    assert got["due"] == 2
    assert got["requests"] == 1 and got["within_ttft"] == 1
    assert got["remainder_p50_ms"] == pytest.approx(250 - 10 - 30)
    assert got["queue_p95_ms"] == pytest.approx(10)


@pytest.fixture(scope="module")
def recorded():
    d = json.loads((DATA / "cnn_b1_trace.json").read_text())
    ev = lambda rows: [Event(n, s, du) for n, s, du in rows]
    base = dict(ops=ev(d["ops"]), programs=ev(d["programs"]),
                host=ev(d["host"]), n_devices=1, window_s=1.0)
    lo = min(e.start for e in base["programs"]) - 1 * MS
    calls = [_span("model.put", p.start - 0.3 * MS, p.start - 0.1 * MS,
                   2 * i + 1) for i, p in enumerate(base["programs"])]
    return xtrace.Trace(**base), spans.SpanTrace(
        **base, spans=calls, lo_ns=lo, hi_ns=lo + 1.0 * S)


class _Ctx:
    model = {"convs": [(226, 3, 64, 3), (224, 64, 64, 3)]}
    mix = {"batch": 1}
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("read", [
    lambda t: t.busy_s, lambda t: t.idle_share(),
    lambda t: t.breakdown()["device_ops"],
    lambda t: t.breakdown()["idle_gaps"],
    lambda t: lm_trace_runs(t),
    lambda t: _reader("device_idle_share.cnn").read(_ctx_for(t)),
    lambda t: _reader("conv_roofline.cnn").read(_ctx_for(t))],
    ids=["busy_s", "idle_share", "device_ops", "idle_gaps", "decode_runs",
         "device_idle_share", "conv_roofline"])
def test_recorded_trace_reads_the_same_with_spans(recorded, read):
    plain, with_spans = recorded
    assert read(with_spans) == read(plain)


def lm_trace_runs(t):
    from bench.lib import lm_trace
    return lm_trace.decode_runs(t)


def _ctx_for(t):
    c = _Ctx()
    c.trace = t
    return c


def test_recorded_trace_idle_by_span(recorded):
    _, t = recorded
    got = dict(t.breakdown()["idle_by_span"])
    assert set(got) == {"model.put", "(no span)"}
    # each call's put is idle for its 0.2 ms before the chain starts
    assert got["model.put"] == pytest.approx(50 * 0.2e-3, rel=1e-6)
    idle = 1.0 - float(spans.Busy(t.ops).within(t.lo_ns, t.hi_ns)) / S
    assert sum(got.values()) == pytest.approx(idle)


@pytest.mark.parametrize("cell,seconds", [("cnn_vgg16.b1", 0.5),
                                          ("lm_qwen25_3b.chat", 2.0)])
def test_traced_run_with_spans_on_the_cpu(cell, seconds):
    c = tiny.cell(cell)
    # the CPU has no peak in the table: read the span metrics alone
    r = bench_spans.run_cell(dict(tiny.spec(), per_layer=[]), c,
                             seed=tiny.SEED, seconds=seconds,
                             t_start=time.monotonic(),
                             clock=harness.CompileClock(),
                             config=tiny.config(c["config"]),
                             mix=tiny.mix(c["traffic"]))
    assert r["correct"], r["checks"]
    assert "idle_by_span" in r["breakdown"]
    assert tracing.now() is None          # the recorder is off again
    if cell.startswith("cnn"):
        assert r["spans"]["model.put"] == r["spans"]["model.dispatch"] > 0
        assert r["ttft_split"] is None
    else:
        assert set(r["metrics"]) == {"queue_wait_p95_ms.lm_chat",
                                     "prefill_p95_ms.lm_chat",
                                     "step_host_ms_p50.lm_chat",
                                     "decode_batch_mean.lm_chat"}
        split = r["ttft_split"]
        assert split["due"] == split["requests"] > 0
        assert split["within_ttft"] == split["requests"]


def test_span_metrics_are_benchmark_entries():
    got = bench_spans.span_metrics()
    spec = tiny.spec()
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    layers = {m["layer"] for m in spec["per_layer"]}
    assert not {m["name"] for m in got} & {m["name"]
                                           for m in spec["per_layer"]}
    for m in got:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] == "program_span"
        assert set(m["workloads"]) <= cells
        assert all(w in e2e[m["moves"]]["workloads"] for w in m["workloads"])
        assert m["layer"] in layers | {"request front", "prefill"}


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/spans.py", "--workload",
                        "cnn_vgg16.b1", "--seed", "1", "--seconds", "1"],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 3
    assert "nothing was measured" in p.stderr
    assert "correct" not in p.stdout
