"""The async ``CodrBatchServer`` path: futures parity with the sync
bucketed dispatch, deadline/max-batch flush triggers, out-of-order
completion across shape buckets, exception propagation into exactly the
failed batch's futures, and stop/drain/restart semantics.

Timing-sensitive assertions are one-sided (an event happens within a
generous timeout) so the file stays deterministic on loaded CI boxes.
"""
import threading

import numpy as np
import pytest

import repro.api as codr


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _sparse(rng, shape, density=0.5, scale=0.5):
    w = rng.normal(size=shape).astype(np.float32) * scale
    w[rng.random(shape) > density] = 0
    return w


@pytest.fixture(scope="module")
def compiled():
    """Tiny conv-only model (conv-only → any input spatial size works,
    which the mixed-shape tests need)."""
    rng = np.random.default_rng(7)
    w = rng.normal(size=(6, 3, 3, 3)).astype(np.float32) * 0.5
    w[rng.random(w.shape) > 0.5] = 0
    spec = codr.ModelSpec([codr.LayerSpec.conv(
        w, rng.normal(size=6).astype(np.float32), activation="relu",
        name="c0")])
    return codr.compile(spec, codr.EncodeConfig(n_unique=16))


def test_async_matches_sync_bit_for_bit(compiled, rng):
    """submit_async resolves to exactly what the sync path produces for
    the same request stream (same bucketing → same batch shapes →
    identical float bits)."""
    xs = [rng.normal(size=(9, 9, 3)).astype(np.float32) for _ in range(11)]
    refs = compiled.serve(max_batch=4).serve(xs)
    server = compiled.serve(max_batch=4, flush_deadline_s=0.05)
    with server:
        futs = [server.submit_async(x) for x in xs]
        outs = [f.result(timeout=120) for f in futs]
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    assert server.requests_served == len(xs)
    assert server.async_pending == 0


def test_deadline_triggers_partial_flush(compiled, rng):
    """A single request far below max_batch must still be served — the
    latency trigger flushes a partial batch after flush_deadline_s."""
    server = compiled.serve(max_batch=64, flush_deadline_s=0.05)
    fut = server.submit_async(rng.normal(size=(9, 9, 3)).astype(np.float32))
    out = fut.result(timeout=120)               # resolves ⇒ deadline fired
    assert out.shape == (7, 7, 6)
    assert server.batches_run == 1
    assert server.bucket_counts == {1: 1}       # partial: bucket of 1
    server.stop_async()


def test_max_batch_triggers_before_deadline(compiled, rng):
    """With an hour-long deadline, a full batch must dispatch on the
    load trigger — futures resolving at all proves it wasn't the
    deadline."""
    server = compiled.serve(max_batch=4, flush_deadline_s=3600.0)
    xs = [rng.normal(size=(9, 9, 3)).astype(np.float32) for _ in range(4)]
    futs = [server.submit_async(x) for x in xs]
    outs = [f.result(timeout=120) for f in futs]
    assert all(o.shape == (7, 7, 6) for o in outs)
    assert server.bucket_counts.get(4) == 1
    server.stop_async(drain=False)


def test_out_of_order_completion_across_shape_buckets(compiled, rng):
    """Mixed-shape streams complete per shape bucket, not in submission
    order; every future still gets its own sample's output."""
    a = [rng.normal(size=(9, 9, 3)).astype(np.float32) for _ in range(3)]
    b = [rng.normal(size=(11, 11, 3)).astype(np.float32) for _ in range(2)]
    order = []                                  # completion order, by tag
    done = threading.Event()

    def track(tag):
        def cb(fut):
            order.append(tag)
            if len(order) == 5:
                done.set()
        return cb

    # max_batch far above the submission count: neither trigger can fire
    # mid-submission, so the whole queue dispatches as one drained flush
    server = compiled.serve(max_batch=64, flush_deadline_s=3600.0)
    server.start_async()
    # interleave: a0 b0 a1 b1 a2 — then drain via stop
    futs, tags = [], []
    for i, (x, tag) in enumerate(zip(
            [a[0], b[0], a[1], b[1], a[2]],
            ["a0", "b0", "a1", "b1", "a2"])):
        f = server.submit_async(x)
        f.add_done_callback(track(tag))
        futs.append(f)
        tags.append(tag)
    server.stop_async(drain=True)
    assert done.wait(timeout=120)
    # chunks dispatch grouped by shape: [a0,a1,a2] then [b0,b1] — so a2
    # (submitted last) completes before b0 (submitted second)
    assert order.index("a2") < order.index("b0")
    # ...and every future carries its own sample's result (sync refs use
    # the same max_batch so the batch shapes — hence float bits — match)
    refs_a = compiled.serve(max_batch=64).serve(a)
    refs_b = compiled.serve(max_batch=64).serve(b)
    refs = {"a0": refs_a[0], "a1": refs_a[1], "a2": refs_a[2],
            "b0": refs_b[0], "b1": refs_b[1]}
    for f, tag in zip(futs, tags):
        np.testing.assert_array_equal(f.result(timeout=1), refs[tag])


def test_exception_propagates_to_failed_batch_only(compiled, rng):
    """A malformed sample poisons exactly its own batch's futures; other
    batches and the flush loop survive."""
    server = compiled.serve(max_batch=2, flush_deadline_s=0.02)
    bad = rng.normal(size=(9, 9, 4)).astype(np.float32)  # 4 chans, model
    fut_bad = server.submit_async(bad)                   # expects 3 → dies
    with pytest.raises(Exception):
        fut_bad.result(timeout=120)
    # the loop is still alive and serving
    good = rng.normal(size=(9, 9, 3)).astype(np.float32)
    fut_good = server.submit_async(good)
    ref = np.asarray(compiled.run(good[None]))[0]
    np.testing.assert_array_equal(fut_good.result(timeout=120), ref)
    server.stop_async()


def test_failed_transfer_fails_its_batch_not_the_device(compiled, rng,
                                                        monkeypatch):
    """A failed host→device transfer is not replaced by the host array:
    exactly that batch's futures fail with it, and the loop serves on."""
    import jax
    real_put = jax.device_put

    def put(x, *args, **kwargs):
        if isinstance(x, np.ndarray) and (x == 12345.0).any():
            raise RuntimeError("transfer failed")
        return real_put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", put)
    server = compiled.serve(max_batch=2, flush_deadline_s=0.02)
    fut_bad = server.submit_async(np.full((9, 9, 3), 12345.0, np.float32))
    with pytest.raises(RuntimeError, match="transfer failed"):
        fut_bad.result(timeout=120)
    good = rng.normal(size=(9, 9, 3)).astype(np.float32)
    fut_good = server.submit_async(good)
    ref = np.asarray(compiled.run(good[None]))[0]
    np.testing.assert_array_equal(fut_good.result(timeout=120), ref)
    server.stop_async()


def test_stop_drain_false_cancels_and_restart_works(compiled, rng):
    server = compiled.serve(max_batch=64, flush_deadline_s=3600.0)
    x = rng.normal(size=(9, 9, 3)).astype(np.float32)
    fut = server.submit_async(x)
    server.stop_async(drain=False)
    assert fut.cancelled()
    # restart: the next submit lazily brings the loop back up
    fut2 = server.submit_async(x)
    server.stop_async(drain=True)
    np.testing.assert_array_equal(fut2.result(timeout=1),
                                  np.asarray(compiled.run(x[None]))[0])


def test_individually_cancelled_future_skips_compute(compiled, rng):
    """A future cancelled while queued is dropped before batching: it
    stays cancelled, burns no compute, and never counts as served."""
    server = compiled.serve(max_batch=64, flush_deadline_s=3600.0)
    xs = [rng.normal(size=(9, 9, 3)).astype(np.float32) for _ in range(2)]
    f_cancel = server.submit_async(xs[0])
    f_keep = server.submit_async(xs[1])
    assert f_cancel.cancel()
    server.stop_async(drain=True)
    assert f_cancel.cancelled()
    np.testing.assert_array_equal(
        f_keep.result(timeout=1),
        compiled.serve(max_batch=64).serve([xs[1]])[0])
    assert server.requests_served == 1
    assert server.bucket_counts == {1: 1}


def test_context_manager_drains_on_exit(compiled, rng):
    xs = [rng.normal(size=(9, 9, 3)).astype(np.float32) for _ in range(3)]
    server = compiled.serve(max_batch=64, flush_deadline_s=3600.0)
    with server:
        futs = [server.submit_async(x) for x in xs]
    # __exit__ = stop_async(drain=True): everything resolved, no waiting
    refs = compiled.serve(max_batch=64).serve(xs)
    for f, r in zip(futs, refs):
        np.testing.assert_array_equal(f.result(timeout=1), r)


def test_sync_flush_unaffected_by_async_state(compiled, rng):
    """The sync and async queues are independent: a running flush loop
    never steals synchronously submitted requests."""
    server = compiled.serve(max_batch=4, flush_deadline_s=0.01)
    server.start_async()
    x = rng.normal(size=(9, 9, 3)).astype(np.float32)
    rid = server.submit(x)
    assert rid == 0
    import time
    time.sleep(0.05)                    # give the loop a chance to misbehave
    outs = server.flush()
    assert len(outs) == 1 and outs[0].shape == (7, 7, 6)
    server.stop_async()
