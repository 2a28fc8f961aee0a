"""The trace reduction: on a small hand-made trace whose answers are
known, and on a small recorded one (50 calls of the VGG16 stage at
batch 1 on a TPU v5e): busy union, idle share, kernel time, gaps
between programs, idle time by host activity."""
import json
from pathlib import Path

import pytest

from bench.lib import cnn_trace, lm_trace, xtrace
from bench.lib.xtrace import Event

DATA = Path(__file__).parent / "data"
MS = 1e6
CODR = ("%codr_matmul_pallas.48 = f32[8,128,256] custom-call(f32[128,11008] "
        "%multiply_convert_fusion.2, s32[11008,256] %bitcast-convert_bitcast"
        "_fusion.20, f32[16] %fusion.155, f32[1] %dynamic_slice.204), custom"
        "_call_target=\"tpu_custom_call\"")
CODR_DECODE = ("%codr_matmul_pallas.67 = f32[8,32,1376]{2,1,0:T(8,128)S(1)} "
               "custom-call(f32[32,2048]{1,0:T(8,128)S(1)} %convert_bitcast_"
               "fusion.26, s32[2048,1376]{1,0:T(8,128)S(1)} %bitcast-convert"
               "_bitcast_fusion.18, f32[16]{0} %fusion.1)")


def small_trace():
    ops = [Event("%fusion.1 = f32[2] fusion()", 0, 2 * MS),
           Event(CODR_DECODE, 1 * MS, 3 * MS),
           Event("%fusion.2 = f32[2] fusion()", 6 * MS, 1 * MS),
           Event(CODR, 10 * MS, 2 * MS),
           Event("%copy.3 = f32[2] copy()", 15 * MS, 1 * MS)]
    programs = [Event("jit_step(1)", 0, 4 * MS),
                Event("jit_prefill(2)", 6 * MS, 1 * MS),
                Event("jit_step(1)", 10 * MS, 2 * MS),
                Event("jit_step(1)", 15 * MS, 1 * MS)]
    host = [Event("thread", 0, 20 * MS), Event("argmax", 4 * MS, 2 * MS),
            Event("push_table", 7 * MS, 3 * MS),
            Event("sleep", 12 * MS, 3 * MS)]
    return xtrace.Trace(ops=ops, programs=programs, host=host, n_devices=1,
                        window_s=0.020)


def test_busy_union_and_idle_share():
    t = small_trace()
    # union: [0,4) + [6,7) + [10,12) + [15,16) = 8 ms of 20
    assert xtrace.busy_union(t.ops) == pytest.approx(8 * MS)
    assert t.busy_s == pytest.approx(0.008)
    assert t.idle_share() == pytest.approx(0.6)


@pytest.mark.parametrize("metric", ["device_idle_share.cnn",
                                    "device_idle_share.lm_backlog"])
def test_idle_share_readers_read_the_trace_alone(metric):
    from bench.lib import harness
    mod = harness.load_module(harness.BENCH_DIR / "metrics" / f"{metric}.py",
                              "idle_reader")
    ctx = harness.Ctx(cell={}, config={}, mix={}, seconds=51.0,
                      device_kind="TPU v5 lite", setup_s=1.0, t0=0.0,
                      t1=51.0, requests=[], calls=[], model={},
                      trace=small_trace())
    assert mod.read(ctx) == pytest.approx(60.0)
    ctx.trace = None
    assert mod.read(ctx) is None


def test_idle_gaps_and_clipped_busy():
    t = small_trace()
    assert xtrace.idle_gaps(t.ops) == [(4 * MS, 6 * MS), (7 * MS, 10 * MS),
                                       (12 * MS, 15 * MS)]
    assert xtrace.busy_within(t.ops, 3 * MS, 11 * MS) == \
        pytest.approx(3 * MS)


def test_kernel_calls_time_and_shapes():
    t = small_trace()
    calls = lm_trace.codr_calls(t)
    assert [(m, k, n, b) for _, m, k, n, b in calls] == \
        [(32, 2048, 11008, 4), (128, 11008, 2048, 4)]
    assert sum(e.dur for e, *_ in calls) == pytest.approx(5 * MS)
    # the decode call is bound by bytes, the other by its operations
    least = max(2 * 32 * 2048 * 11008 / 197e12,
                (2048 * 11008 / 2 + (32 * 2048 + 32 * 11008) * 2) / 819e9) \
        + max(2 * 128 * 11008 * 2048 / 197e12,
              (11008 * 2048 / 2 + (128 * 11008 + 128 * 2048) * 2) / 819e9)
    assert lm_trace.codr_roofline(t, "TPU v5 lite") == \
        pytest.approx(100 * least / 5e-3)


def test_decode_runs_and_the_gaps_between_them():
    t = small_trace()
    runs = lm_trace.decode_runs(t)
    # the runs of jit_step that hold a kernel (twice; the third holds
    # none); the prefill program holds none either
    assert [r.start for r in runs] == [0, 10 * MS]
    idle, total = xtrace.start_to_start_idle(t, runs)
    # 0->10: busy 4 + 1 of 10
    assert total == pytest.approx(10 * MS)
    assert idle == pytest.approx(5 * MS)
    assert lm_trace.decode_gap_share(t) == pytest.approx(50.0)


def test_idle_goes_to_the_innermost_host_event():
    t = small_trace()
    assert dict(xtrace.idle_by_host(t)) == pytest.approx(
        {"argmax": 0.002, "push_table": 0.003, "sleep": 0.003})
    b = t.breakdown()
    assert b["device_ops"][0] == ["%codr_matmul_pallas.67 = f32[8,32,1376]"
                                  " custom-call", pytest.approx(0.003)]
    assert len(b["idle_gaps"]) == 3


@pytest.fixture(scope="module")
def recorded():
    d = json.loads((DATA / "cnn_b1_trace.json").read_text())
    ev = lambda rows: [Event(n, s, du) for n, s, du in rows]
    t = xtrace.Trace(ops=ev(d["ops"]), programs=ev(d["programs"]),
                     host=ev(d["host"]), n_devices=1, window_s=1.0)
    return t


def test_recorded_trace_busy_union(recorded):
    t = recorded
    # one device line: ops never overlap, so the union is their sum, and
    # every op lies inside one of the 50 program runs
    assert len(t.programs) == 50 and len(t.ops) == 600
    assert xtrace.busy_union(t.ops) == pytest.approx(
        sum(e.dur for e in t.ops))
    assert xtrace.busy_union(t.ops) <= sum(p.dur for p in t.programs)
    span = max(e.end for e in t.ops) - min(e.start for e in t.ops)
    gaps = sum(hi - lo for lo, hi in xtrace.idle_gaps(t.ops))
    assert xtrace.busy_union(t.ops) + gaps == pytest.approx(span)


def test_recorded_trace_conv_kernels(recorded):
    class Ctx:
        trace = recorded
        model = {"convs": [(226, 3, 64, 3), (224, 64, 64, 3)]}
        mix = {"batch": 1}
        device_kind = "TPU v5 lite"
    ops = cnn_trace.conv_ops(recorded, Ctx.model["convs"])
    assert len(ops) == 100          # two convolutions per call
    flops, nbytes = cnn_trace.chain_work(Ctx.model["convs"], 1)
    assert flops == pytest.approx(3.81e9, rel=1e-3)
    share = cnn_trace.conv_roofline(Ctx)
    least = max(flops / 197e12, nbytes / 819e9)
    assert share == pytest.approx(100 * least * 50 /
                                  (sum(e.dur for e in ops) / 1e9))
    assert 0 < share < 100
    labels = dict(recorded.breakdown()["idle_gaps"])
    assert max(labels, key=labels.get) == "XlaLinearize"
