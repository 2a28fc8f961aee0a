"""The in-program span recorder (``repro.runtime.tracing``) and the spans
of the two served paths: off it records nothing; on, spans nest per
thread, the buffer keeps the newest, and spans may cross threads.  A
``ContinuousBatcher`` records one queue and one prefill span per
request and one step span with its five children per pooled step, and
serves the same bits with the recorder on as off; ``tiled`` records the
put and the dispatch of each call.  A failed push fails only its batch.
The jits are named for what they run."""
import threading
import time

import jax
import numpy as np
import pytest

import repro.api as codr
from repro.configs import get_config, smoke_variant
from repro.core.batching import ContinuousBatcher
from repro.runtime import resilience, tracing

STEP_CHILDREN = ["batcher.push", "batcher.dispatch", "batcher.wait",
                 "batcher.fetch", "batcher.sample"]


@pytest.fixture(autouse=True)
def recorder():
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()


@pytest.fixture(scope="module")
def lm():
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    from repro.models import get_model
    return cfg, get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)


def _span(name):
    with tracing.span(name, rid=1, k=2) as s:
        s.set(more=3)


def _record(name):
    tracing.record(name, 1, 2, rid=1, k=2)


def _now(name):
    assert tracing.now() is None


@pytest.mark.parametrize("site", [_span, _record, _now])
def test_off_records_nothing(site):
    site("x")
    assert tracing.now() is None
    assert tracing.drain() == []


def test_nesting_parents_and_attrs():
    tracing.enable()
    with tracing.span("a", rid=7, n=1) as a:
        with tracing.span("b"):
            pass
        with tracing.span("c") as c:
            c.set(late=True)
    got = {s.name: s for s in tracing.drain()}
    assert set(got) == {"a", "b", "c"}
    assert got["a"].parent is None and got["a"].rid == 7
    assert got["a"].attrs == {"n": 1}
    assert got["b"].parent == got["c"].parent == got["a"].id == a.id
    assert got["c"].attrs == {"late": True}
    assert got["a"].start_ns <= got["b"].start_ns <= got["b"].end_ns \
        <= got["c"].start_ns <= got["c"].end_ns <= got["a"].end_ns
    assert got["a"].thread == threading.get_ident()
    assert len({s.id for s in got.values()}) == 3


def test_error_and_discard():
    tracing.enable()
    with pytest.raises(KeyError):
        with tracing.span("fails"):
            raise KeyError("x")
    with tracing.span("idle") as s:
        s.discard()
    with tracing.span("after") as after:
        pass
    got = tracing.drain()
    assert [s.name for s in got] == ["fails", "after"]
    assert got[0].attrs == {"error": "KeyError"}
    assert got[1].parent is None and got[1].id == after.id


def test_capacity_keeps_the_newest():
    tracing.enable(capacity=4)
    for i in range(10):
        with tracing.span(f"s{i}"):
            pass
    assert [s.name for s in tracing.drain()] == ["s6", "s7", "s8", "s9"]
    assert tracing.drain() == []


def test_record_across_threads():
    tracing.enable()
    start = tracing.now()
    with tracing.span("outer"):
        t = threading.Thread(target=lambda: tracing.record(
            "queue", start, tracing.now(), rid=3, prompt_len=5))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    q, outer = tracing.drain()
    assert (q.name, outer.name) == ("queue", "outer")
    assert q.parent is None and q.thread is None and q.rid == 3
    assert q.attrs == {"prompt_len": 5} and q.start_ns <= q.end_ns


def test_threads_nest_apart():
    tracing.enable()
    barrier = threading.Barrier(4)

    def work(k):
        with tracing.span(f"top{k}"):
            barrier.wait(timeout=10)
            for _ in range(50):
                with tracing.span(f"child{k}"):
                    pass
    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    got = tracing.drain()
    tops = {s.name: s for s in got if s.name.startswith("top")}
    assert len(got) == 4 * 51
    for s in got:
        if s.name.startswith("child"):
            top = tops["top" + s.name[5:]]
            assert s.parent == top.id and s.thread == top.thread


def _serve(cb, prompts, n):
    handles = [cb.submit(p, max_new_tokens=n) for p in prompts]
    return handles, [h.result(timeout=300) for h in handles]


@pytest.mark.parametrize("kv", [{}, {"kv_dtype": "int8"}],
                         ids=["bf16", "int8_paged"])
def test_batcher_spans_and_the_same_bits(lm, kv):
    cfg, params = lm
    cb = ContinuousBatcher(params, cfg, n_slots=4, max_len=32,
                           record_logits=True, **kv)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 5, 4, 7, 6, 2)]   # six requests, four slots
    off_h, off = _serve(cb, prompts, 6)
    steps_before = cb.steps_run
    tracing.enable()
    on_h, on = _serve(cb, prompts, 6)
    cb.stop_async()
    tracing.disable()
    got = tracing.drain()
    assert on == off
    for a, b in zip(on_h, off_h):
        assert len(a.logits) == len(b.logits)
        for x, y in zip(a.logits, b.logits):
            np.testing.assert_array_equal(x, y)

    for h, p in zip(on_h, prompts):
        for name in ("batcher.queue", "batcher.prefill"):
            mine = [s for s in got if s.name == name and s.rid == h.rid]
            assert len(mine) == 1, (name, h.rid)
            assert mine[0].attrs["prompt_len"] == len(p)
    steps = [s for s in got if s.name == "batcher.step"]
    assert len(steps) == cb.steps_run - steps_before
    for st in steps:
        kids = sorted((s for s in got if s.parent == st.id),
                      key=lambda s: s.start_ns)
        assert [s.name for s in kids] == STEP_CHILDREN
        assert all(st.start_ns <= k.start_ns <= k.end_ns <= st.end_ns
                   for k in kids)
        assert 1 <= st.attrs["n_active"] <= 4
    # every token after the first comes from one active slot of a step
    assert sum(s.attrs["n_active"] for s in steps) == \
        sum(len(t) - 1 for t in on)


@pytest.mark.parametrize("backend", ["codr_matmul", "tiled", None],
                         ids=["codr_matmul", "tiled", "dense"])
def test_batcher_prefill_weight_passes(lm, backend):
    """A prefill on ``codr_matmul`` packs records how many times its
    kernel calls decode each weight block, the kernel's own
    ``row_blocks``; on other packs or dense params it records none."""
    from repro.kernels.codr_matmul.kernel import row_blocks
    cfg, params = lm
    if backend is not None:
        params = codr.compile_params(params, codr.EncodeConfig(n_unique=16),
                                     backend=backend)
    cb = ContinuousBatcher(params, cfg, n_slots=2, max_len=32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 9)]
    tracing.enable()
    _serve(cb, prompts, 1)
    cb.stop_async()
    tracing.disable()
    pre = [s for s in tracing.drain() if s.name == "batcher.prefill"]
    assert sorted(s.attrs["prompt_len"] for s in pre) == [3, 9]
    for s in pre:
        if backend == "codr_matmul":
            assert s.attrs["weight_passes"] == \
                row_blocks(s.attrs["prompt_len"]) == 1
        else:
            assert "weight_passes" not in s.attrs


def test_batcher_retry_records_each_try_under_its_step(lm):
    cfg, params = lm
    cb = ContinuousBatcher(params, cfg, n_slots=2, max_len=32)
    plan = resilience.FaultPlan([resilience.Fault("batcher.decode", 0)])
    cb.configure_resilience(
        injector=resilience.FaultInjector(plan),
        retry_policy=resilience.RetryPolicy(max_retries=2, backoff_s=0.0))
    prompt = np.arange(1, 6, dtype=np.int32)
    tracing.enable()
    out = cb.submit(prompt, max_new_tokens=3).result(timeout=300)
    cb.stop_async()
    tracing.disable()
    got = tracing.drain()
    assert out == cb.generate_reference(prompt, max_new_tokens=3)[0]
    first = min((s for s in got if s.name == "batcher.step"),
                key=lambda s: s.start_ns)
    kids = sorted((s for s in got if s.parent == first.id),
                  key=lambda s: s.start_ns)
    # the failed try pushed and dispatched; the retry ran the whole step
    assert [s.name for s in kids] == ["batcher.push", "batcher.dispatch"] \
        + STEP_CHILDREN
    assert kids[1].attrs == {"error": "InjectedFault"}
    assert "error" not in first.attrs


class _FailingUpload:
    """``jnp`` for the batcher, with the next upload of a step's tokens
    (an int32 vector of one entry per slot) failing once."""

    def __init__(self, n_slots):
        self.n_slots, self.armed = n_slots, True

    def __getattr__(self, name):
        return getattr(jax.numpy, name)

    def asarray(self, a, *args, **kw):
        if self.armed and isinstance(a, np.ndarray) \
                and a.shape == (self.n_slots,):
            self.armed = False
            raise RuntimeError("injected push failure")
        return jax.numpy.asarray(a, *args, **kw)


@pytest.mark.parametrize("where", ["tokens", "page_table"])
def test_failed_push_fails_only_its_batch(lm, monkeypatch, where):
    from repro.core import batching
    from repro.models import cache
    cfg, params = lm
    cb = ContinuousBatcher(params, cfg, n_slots=4, max_len=32,
                           kv_dtype="int8")
    if where == "tokens":
        monkeypatch.setattr(batching, "jnp", _FailingUpload(4))
    else:
        set_tables, calls = cache.set_tables, []

        def failing(pool, table):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("injected push failure")
            return set_tables(pool, table)
        monkeypatch.setattr(cache, "set_tables", failing)
    prompt = np.arange(1, 6, dtype=np.int32)
    tracing.enable()
    with pytest.raises(RuntimeError, match="injected push failure"):
        cb.submit(prompt, max_new_tokens=3).result(timeout=300)
    # the worker lived on: the next request is served, and right
    out = cb.submit(prompt, max_new_tokens=3).result(timeout=300)
    cb.stop_async()
    tracing.disable()
    assert cb.worker_crashes == 0
    assert out == cb.generate_reference(prompt, max_new_tokens=3)[0]
    steps = [s for s in tracing.drain() if s.name == "batcher.step"]
    assert [s.attrs.get("error") for s in steps].count("RuntimeError") == 1


def test_batcher_queue_spans_of_shed_and_expired_requests(lm):
    cfg, params = lm
    cb = ContinuousBatcher(params, cfg, n_slots=1, max_len=32,
                           max_pending=1)
    prefill = cb._prefill_fn

    def slow_prefill(p, t):     # holds the one slot while others queue
        time.sleep(0.3)
        return prefill(p, t)
    cb._prefill_fn = slow_prefill
    prompt = np.arange(1, 5, dtype=np.int32)
    tracing.enable()
    a = cb.submit(prompt, max_new_tokens=2)
    deadline = time.monotonic() + 60
    while cb.active < 1 and time.monotonic() < deadline:
        time.sleep(0.001)
    b = cb.submit(prompt, max_new_tokens=2, deadline_s=0.05)
    with pytest.raises(resilience.RejectedError):
        cb.submit(prompt, max_new_tokens=2)
    a.result(timeout=300)
    with pytest.raises(resilience.DeadlineExceeded):
        b.result(timeout=300)
    cb.stop_async()
    got = {s.attrs.get("outcome"): s for s in tracing.drain()
           if s.name == "batcher.queue"}
    assert set(got) == {None, "expired", "shed"}
    assert got[None].rid == a.rid and got["expired"].rid == b.rid
    assert got["shed"].rid is None
    assert got["expired"].dur_ns >= 50e6 > got["shed"].dur_ns


def test_jits_are_named(lm):
    cfg, params = lm
    cb = ContinuousBatcher(params, cfg, n_slots=2, max_len=32,
                           kv_dtype="int8")
    assert "module @jit_decode_step" in cb.lower_decode_step().as_text()
    assert cb._prefill_fn.__name__ == "prefill"
    assert cb._write_fn.__name__ == "write_slot"


def test_tiled_records_put_and_dispatch():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(4, 3, 3, 3)).astype(np.float32)
    w[rng.random(w.shape) < 0.5] = 0.0
    model = codr.compile(codr.ModelSpec([codr.LayerSpec.conv(w)]),
                         backend="tiled")
    x = rng.normal(size=(2, 9, 9, 3)).astype(np.float32)
    y_off = np.asarray(model.run(x))
    tracing.enable()
    y_on = np.asarray(model.run(x))
    got = tracing.drain()
    np.testing.assert_array_equal(y_on, y_off)
    assert [(s.name, s.attrs) for s in got] == [
        ("model.put", {"batch": 2}), ("model.dispatch", {})]
    lowered = model.model._run_tiled.lower(jax.numpy.asarray(x))
    assert "module @jit_cnn_chain" in lowered.as_text()
