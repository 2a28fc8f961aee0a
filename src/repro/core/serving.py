"""CoDR weight compression as a serving feature.

``codr_compress_params`` runs the paper's offline pipeline over every
projection matrix in a params pytree: int8 quantization → unique-weight
budget U (the paper's Fig. 6 U-sweep knob) → UCR (sort/densify/unify/Δ)
→ customized RLE parameter search.  It returns

  * params with the quantization *applied* (so served logits reflect the
    compressed weights — what you'd get decoding the real bitstream), and
  * a per-tensor report of real encoded bits (CoDR) vs UCNN / SCNN / int8
    / the fixed-width kernel pack.

The decode-fused execution lives in ``repro.kernels.codr_matmul`` (run
on TPU; interpret-mode on CPU) — the XLA serving graphs model compressed
weights as int8 + scale (docs/DESIGN.md §2 explains the split).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent import futures

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rle, ucr
from repro.core.baselines import scnn_compress_bits, ucnn_compress_bits
from repro.core.codr_linear import choose_bits
from repro.core.ucr import restrict_unique  # noqa: F401  (canonical home)

MIN_COMPRESS_SIZE = 1024           # skip tiny leaves (norms, biases)


@dataclasses.dataclass
class TensorReport:
    """Per-tensor compression accounting.  ``codr/ucnn/scnn_bits`` are
    the variable-width storage formats; ``pack_bits`` is the size of the
    **fixed-width unique-index pack** the decode-fused kernel executes
    from — i.e. the weight HBM traffic of the serving path, which is why
    it rides in the report instead of being recomputed downstream."""

    path: str
    n_weights: int
    codr_bits: int
    ucnn_bits: int
    scnn_bits: int
    density: float
    n_unique_mean: float
    pack_bits: int = 0

    @property
    def codr_bits_per_weight(self) -> float:
        return self.codr_bits / self.n_weights

    @property
    def pack_bits_per_weight(self) -> float:
        return self.pack_bits / self.n_weights


def compress_tensor(w: np.ndarray, *, n_unique: int = 256, t_m: int = 256
                    ) -> tuple[np.ndarray, dict]:
    """Offline CoDR pipeline for one (d_in, d_out) matrix.  Returns the
    dequantized-after-restriction tensor + size accounting."""
    q, scale = ucr.quantize_int8(w)
    q = restrict_unique(q, n_unique)
    # UCR per output-column-tile vector (linear layer = 1×1-kernel conv)
    ucrs = []
    m, n = q.shape[1], q.shape[0]       # weights stored (d_in, d_out)
    qt = q.T                            # (M=d_out, N=d_in)
    for m0 in range(0, m, t_m):
        tile = qt[m0 : m0 + t_m]
        for nn in range(n):
            ucrs.append(ucr.ucr_transform(tile[:, nn]))
    codr_bits = rle.layer_bits_size_only(ucrs, min(t_m, m))
    report = {
        "codr_bits": codr_bits,
        "ucnn_bits": ucnn_compress_bits(ucrs),
        "scnn_bits": scnn_compress_bits(q),
        "density": float((q != 0).mean()),
        "n_unique_mean": float(np.mean([len(u.unique_vals) for u in ucrs])),
        "pack_bits": int(q.size) * choose_bits(
            max(int(len(np.unique(q))), 2)),
    }
    deq = ucr.dequantize_int8(q, scale)
    return deq.astype(np.float32), report


def account_tensor(mat: np.ndarray, *, n_unique: int,
                   sample_rows: int | None) -> dict:
    """Sampled RLE/baseline accounting for one ``(rows, d_out)`` matrix:
    encode the leading ``sample_rows`` rows, scale the bit counts back up
    by the sampled fraction.  Shared by ``codr_compress_params`` and
    ``api.compile_params`` so the sampling policy lives in one place."""
    rows = mat.shape[0]
    if sample_rows and rows > sample_rows:
        sub, scale_f = mat[:sample_rows], rows / sample_rows
    else:
        sub, scale_f = mat, 1.0
    _, rep = compress_tensor(sub, n_unique=n_unique)
    out = {k: int(rep[k] * scale_f)
           for k in ("codr_bits", "ucnn_bits", "scnn_bits", "pack_bits")}
    out["density"] = rep["density"]
    out["n_unique_mean"] = rep["n_unique_mean"]
    return out


def codr_compress_params(params, *, n_unique: int = 16,
                         sample_rows: int | None = 4096,
                         sample_cols: int | None = None):
    """Compress every large 2-D+ leaf; returns (new_params, report).

    ``sample_rows`` bounds the RLE accounting work per tensor: each leaf
    is reshaped to ``(rows, d_out)`` and only the leading ``sample_rows``
    **rows** are RLE-encoded, with the bit counts scaled back up by the
    sampled fraction (a regression test pins sampled-vs-full agreement).
    The *quantization* is always applied to the full tensor.

    ``sample_cols`` is the deprecated name of the same parameter — it
    always sampled rows of the reshaped matrix, never columns.
    """
    if sample_cols is not None:
        import warnings
        warnings.warn("codr_compress_params(sample_cols=...) is "
                      "deprecated — it always sampled leading ROWS of "
                      "the reshaped (rows, d_out) matrix; use "
                      "sample_rows", DeprecationWarning, stacklevel=2)
        sample_rows = sample_cols
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    new_leaves, reports = [], []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        arr = np.asarray(leaf)
        if arr.ndim < 2 or arr.size < MIN_COMPRESS_SIZE:
            new_leaves.append(leaf)
            continue
        mat = arr.reshape(-1, arr.shape[-1])
        acc = account_tensor(mat, n_unique=n_unique,
                             sample_rows=sample_rows)
        full_deq, _ = _quantize_only(mat, n_unique)
        new_leaves.append(jnp.asarray(full_deq.reshape(arr.shape),
                                      dtype=leaf.dtype))
        reports.append(TensorReport(path=pstr, n_weights=arr.size, **acc))
    return jax.tree_util.tree_unflatten(treedef, new_leaves), reports


def _quantize_only(mat: np.ndarray, n_unique: int):
    q, scale = ucr.quantize_int8(mat)
    q = restrict_unique(q, n_unique)
    return ucr.dequantize_int8(q, scale), q


def codr_report(reports: list[TensorReport], *,
                per_tensor: bool = False) -> str:
    """Aggregate compression report; ``per_tensor=True`` appends one row
    per tensor (path, mean unique count, measured CoDR and pack
    bits/weight) so per-leaf tune plans are inspectable at a glance."""
    tot_w = sum(r.n_weights for r in reports)
    tot_codr = sum(r.codr_bits for r in reports)
    tot_ucnn = sum(r.ucnn_bits for r in reports)
    tot_scnn = sum(r.scnn_bits for r in reports)
    tot_pack = sum(r.pack_bits for r in reports)
    lines = [
        f"CoDR weight compression over {len(reports)} tensors "
        f"({tot_w/1e6:.1f}M weights):",
        f"  CoDR : {tot_codr/tot_w:.2f} bits/weight "
        f"({16*tot_w/max(tot_codr,1):.1f}x vs bf16)",
        f"  UCNN : {tot_ucnn/tot_w:.2f} bits/weight "
        f"(CoDR {tot_ucnn/max(tot_codr,1):.2f}x better)",
        f"  SCNN : {tot_scnn/tot_w:.2f} bits/weight "
        f"(CoDR {tot_scnn/max(tot_codr,1):.2f}x better)",
    ]
    if tot_pack:
        lines.append(
            f"  pack : {tot_pack/tot_w:.2f} bits/weight fixed-width "
            f"unique-index pack (serving HBM traffic, "
            f"{16*tot_w/max(tot_pack,1):.1f}x vs bf16)")
    if per_tensor:
        lines.append(f"  {'tensor':<40} {'weights':>9} {'uniq':>6} "
                     f"{'codr b/w':>9} {'pack b/w':>9}")
        for r in reports:
            pack = (f"{r.pack_bits_per_weight:9.2f}" if r.pack_bits
                    else f"{'-':>9}")
            lines.append(f"  {r.path:<40} {r.n_weights:>9} "
                         f"{r.n_unique_mean:6.1f} "
                         f"{r.codr_bits_per_weight:9.2f} {pack}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# async worker chassis (shared by CodrBatchServer and ContinuousBatcher)
# ---------------------------------------------------------------------------

class AsyncWorkerLoop:
    """Condition-variable worker-thread chassis: lazy daemon start,
    stop/drain/restart, and the can't-stop-from-the-worker guard.

    Subclasses provide the actual work:

    * :meth:`_loop` — the worker body.  It must re-check
      ``self._stopping`` under ``self._cv`` and return once stopping
      *and* (when draining) the pending work is gone.
    * :meth:`_cancel_pending_locked` — called under ``self._cv`` by
      ``stop_async(drain=False)`` to drop queued work (cancel futures,
      fail handles, ...).

    All shared state transitions happen under ``self._cv``; subclasses
    must take the same lock for their own queue state so one lock
    orders everything (the PR-6 sync-path race lived exactly in code
    that skipped it).

    **Supervision** (``docs/DESIGN.md`` §3.5): the worker thread runs
    :meth:`_loop` under :meth:`_run_worker`, which catches *any* escape
    — including ``BaseException`` crashes — and, when a
    ``RestartPolicy`` is configured via :meth:`configure_resilience`,
    backs off and re-enters the loop **on the same thread** so every
    pending request survives the crash.  Past the restart budget (or
    with no policy) the crash fails every live future/handle through
    the :meth:`_fail_live_locked` hook, guaranteeing ``result()`` never
    hangs on a dead loop.  ``configure_resilience`` also installs the
    optional fault injector (:meth:`_fire` is the zero-overhead-when-
    disabled site hook), retry policy, and serving supervisor consumed
    by subclasses.
    """

    _thread_name = "async-worker"

    def __init__(self) -> None:
        self._cv = threading.Condition()
        self._worker: threading.Thread | None = None   # guarded-by: _cv
        self._stopping = False                         # guarded-by: _cv
        # -- resilience (all optional; None ⇒ exact pre-resilience path)
        self._injector = None           # runtime.resilience.FaultInjector
        self._retry_policy = None       # runtime.resilience.RetryPolicy
        self._restart_policy = None     # runtime.resilience.RestartPolicy
        self._supervisor = None         # runtime.resilience.ServingSupervisor
        self.worker_crashes = 0                        # guarded-by: _cv
        self.worker_restarts = 0                       # guarded-by: _cv

    # -- subclass hooks -----------------------------------------------------
    def _loop(self) -> None:
        raise NotImplementedError

    def _cancel_pending_locked(self) -> None:
        raise NotImplementedError

    def _fail_live_locked(self, exc: BaseException) -> None:
        """Under ``self._cv``: deliver ``exc`` to every live future /
        handle (pending *and* in-flight) so no caller hangs after the
        worker died for good.  Subclasses with queues must override."""

    # -- resilience ---------------------------------------------------------
    def configure_resilience(self, *, injector=None, retry_policy=None,
                             restart_policy=None, supervisor=None):
        """Install resilience hooks (all optional, from
        ``repro.runtime.resilience``): a :class:`FaultInjector` firing
        at this loop's sites, a :class:`RetryPolicy` for transient
        dispatch failures (exhaustion ⇒ quarantine), a
        :class:`RestartPolicy` for worker crashes, and a
        :class:`ServingSupervisor` for latency-watch + mesh degradation.
        With none installed every code path is byte-identical to the
        unwired loop.  Returns ``self`` for chaining."""
        with self._cv:
            self._injector = injector
            self._retry_policy = retry_policy
            self._restart_policy = restart_policy
            self._supervisor = supervisor
        return self

    def _fire(self, site: str) -> None:
        """Fault-injection site hook: one attribute load + ``None``
        check when disabled — the cost a production dispatch pays."""
        inj = self._injector
        if inj is not None:
            inj.fire(site)

    def _run_worker(self) -> None:
        """Thread target: supervise :meth:`_loop`.  A normal return
        ends the thread; any escape (worker crash — ``Exception`` or
        injected ``BaseException``) consumes one restart from the
        ``RestartPolicy`` budget and re-enters the loop after backoff,
        pending work intact.  Budget exhausted ⇒ fail all live work
        with ``WorkerCrashed`` (chaining the cause) and clear
        ``self._worker`` so a later submit can lazily start fresh."""
        while True:
            try:
                self._loop()
                return
            except BaseException as e:  # noqa: BLE001 — supervision net
                with self._cv:
                    self.worker_crashes += 1
                    pol = self._restart_policy
                    if (pol is not None and not self._stopping
                            and self.worker_restarts < pol.max_restarts):
                        n = self.worker_restarts
                        self.worker_restarts += 1
                    else:
                        from repro.runtime.resilience import WorkerCrashed
                        err = WorkerCrashed(
                            f"{self._thread_name} worker died: {e!r}"
                            + ("" if pol is None else
                               f" (restart budget {pol.max_restarts} "
                               "exhausted)"))
                        err.__cause__ = e
                        # clear the thread slot BEFORE failing waiters:
                        # a woken submitter may immediately resubmit and
                        # must be able to lazily start a fresh worker
                        self._worker = None
                        self._fail_live_locked(err)
                        self._cv.notify_all()
                        return
                time.sleep(pol.delay(n))

    # -- lifecycle ----------------------------------------------------------
    def start_async(self):
        """Start the worker explicitly (idempotent)."""
        with self._cv:
            if self._stopping:
                raise RuntimeError(f"{type(self).__name__} is stopping")
            if self._worker is None or not self._worker.is_alive():
                self._start_locked()
        return self

    def _start_locked(self) -> None:
        self._worker = threading.Thread(target=self._run_worker,
                                        name=self._thread_name,
                                        daemon=True)
        self._worker.start()

    def stop_async(self, *, drain: bool = True) -> None:
        """Stop the worker.  ``drain=True`` (default) lets it finish the
        pending work first; ``drain=False`` cancels pending work.
        Idempotent; the loop can be restarted with :meth:`start_async`
        afterwards.  Must not be called from the worker itself (e.g.
        inside a ``Future`` done-callback, which runs on the worker
        thread) — that raises ``RuntimeError`` without corrupting state.
        """
        with self._cv:
            worker = self._worker
            if worker is threading.current_thread():
                raise RuntimeError(
                    f"stop_async called from the {self._thread_name} "
                    "worker itself (done callbacks run on the worker "
                    "thread) — stop from another thread")
            self._stopping = True
            if not drain:
                self._cancel_pending_locked()
            self._cv.notify_all()
        try:
            if worker is not None:
                worker.join()
        finally:
            with self._cv:
                self._worker = None
                self._stopping = False

    def __enter__(self):
        return self.start_async()

    def __exit__(self, *exc) -> None:
        self.stop_async(drain=True)


# ---------------------------------------------------------------------------
# batched request path over a CoDR engine model
# ---------------------------------------------------------------------------

class FlushDispatchError(RuntimeError):
    """A :meth:`CodrBatchServer.flush` chunk dispatch failed.

    Attributes:
        partial: submission-order output list for the flushed queue —
            rows computed by chunks that succeeded before the failure,
            ``None`` elsewhere.
        failed: queue positions (within the flushed queue) of the
            requests in the chunk whose dispatch raised.  These are
            consumed, not requeued.
        requeued: how many undispatched requests were restored to the
            server queue (they will be served by the next ``flush``).
    """

    def __init__(self, msg: str, *, partial, failed, requeued):
        super().__init__(msg)
        self.partial = partial
        self.failed = failed
        self.requeued = requeued


def _res():
    """Lazy handle on ``repro.runtime.resilience`` — imported only when
    a resilience feature (deadline, shedding, retry, injection) is
    actually exercised, so the plain serving path never pays the
    ``repro.runtime`` import."""
    from repro.runtime import resilience
    return resilience


@dataclasses.dataclass
class _AsyncReq:
    """One queued async request: the sample, its future, and the
    absolute monotonic deadline (``None`` ⇒ no deadline)."""

    sample: np.ndarray
    future: futures.Future
    deadline: float | None = None


class CodrBatchServer(AsyncWorkerLoop):
    """Batched inference over a CoDR executable (a
    :class:`repro.core.engine.CodrModel` or a
    :class:`repro.core.api.CompiledModel` — anything with ``.run``).

    Single-sample requests are queued and executed together in fixed-size
    batches, so every forward pass reuses the one jitted tile-dispatch
    computation per layer — the serving-side complement of the engine's
    encode-once/run-many contract.

    Dispatch is **size-bucketed**: requests are grouped by sample shape,
    and ragged tail batches are padded up to the next power-of-two bucket
    (≤ ``max_batch``) rather than to arbitrary sizes.  A mixed-size
    request stream therefore compiles at most ``len(shapes) ×
    log2(max_batch)+1`` forward variants instead of one per distinct
    ragged size — the compile cache stops thrashing while padding waste
    stays bounded at <2x.

    Two request paths share that dispatch core (``docs/DESIGN.md`` §3):

    * **Synchronous** — :meth:`submit` + :meth:`flush` (or
      :meth:`serve`): the caller owns batching cadence; a dispatch
      failure raises out of ``flush``.
    * **Asynchronous** — :meth:`submit_async` returns a
      :class:`concurrent.futures.Future` immediately; a background flush
      loop dispatches when either ``max_batch`` requests are pending
      (load trigger) or the oldest pending request has waited
      ``flush_deadline_s`` (latency trigger).  Consecutive batches are
      **double-buffered**: batch *i+1*'s host→device transfer is issued
      while batch *i* computes, so the device never idles on the PCIe
      copy.  A dispatch failure propagates into exactly the futures of
      the failed batch; other batches are unaffected.

    The loop starts lazily on first ``submit_async`` (or explicitly via
    :meth:`start_async`) and is joined by :meth:`stop_async` /
    ``with server: ...``.
    """

    _thread_name = "codr-batch-server"

    def __init__(self, model, *, max_batch: int = 8,
                 flush_deadline_s: float = 0.01,
                 max_pending: int | None = None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if flush_deadline_s <= 0:
            raise ValueError("flush_deadline_s must be > 0")
        if max_pending is not None and max_pending < 1:
            raise ValueError("max_pending must be >= 1 (or None)")
        super().__init__()                  # _cv / _worker / _stopping
        self.model = model
        self.max_batch = max_batch
        self.flush_deadline_s = flush_deadline_s
        self.max_pending = max_pending      # bounded admission (None=∞)
        self._queue: list[tuple[np.ndarray, float | None]] = []  # guarded-by: _cv
        self._next_id = 0                   # guarded-by: _cv
        self.batches_run = 0                # guarded-by: _cv
        self.requests_served = 0            # guarded-by: _cv
        self.bucket_counts: dict[int, int] = {}   # guarded-by: _cv
        # -- resilience accounting (docs/DESIGN.md §3.5) ----------------
        self.requests_shed = 0              # guarded-by: _cv
        self.requests_expired = 0           # guarded-by: _cv
        self.requests_quarantined = 0       # guarded-by: _cv
        self.quarantined: list[dict] = []   # guarded-by: _cv
        # -- async state ------------------------------------------------
        self._async_queue: list[_AsyncReq] = []   # guarded-by: _cv
        self._oldest_t: float | None = None       # guarded-by: _cv

    def _bucket(self, n_real: int) -> int:
        b = 1
        while b < n_real:
            b *= 2
        return min(b, self.max_batch)

    def _chunks(self, samples: list[np.ndarray]):
        """Shared batching core: group positions by sample shape, split
        into ≤ ``max_batch`` chunks, pad each to its power-of-two bucket.
        Yields ``(positions, batch, n_real, bucket)`` with ``batch`` a
        stacked host array of ``bucket`` rows."""
        by_shape: dict[tuple, list[int]] = {}
        for pos, x in enumerate(samples):
            by_shape.setdefault(x.shape, []).append(pos)
        for positions in by_shape.values():
            for i in range(0, len(positions), self.max_batch):
                chunk_pos = positions[i : i + self.max_batch]
                chunk = [samples[p] for p in chunk_pos]
                n_real = len(chunk)
                bucket = self._bucket(n_real)
                if n_real < bucket:          # pad → bucketed batch shape
                    chunk = chunk + [chunk[-1]] * (bucket - n_real)
                yield chunk_pos, np.stack(chunk), n_real, bucket

    def _count(self, n_real: int, bucket: int) -> None:
        # locked: the sync flush (caller thread) and the async flush
        # loop (worker thread) both account onto these counters
        with self._cv:
            self.batches_run += 1
            self.requests_served += n_real
            self.bucket_counts[bucket] = \
                self.bucket_counts.get(bucket, 0) + 1

    def _admit_deadline(self, deadline_s: float | None) -> float | None:
        if deadline_s is None:
            return None
        if deadline_s <= 0:
            raise ValueError("deadline_s must be > 0 (or None)")
        return time.monotonic() + deadline_s

    def _shed_locked(self, pending: int) -> None:
        """Under ``self._cv``: reject admission when the bounded queue
        is full (``RejectedError`` with a retry-after hint — one flush
        deadline is when capacity frees up at the latest)."""
        if self.max_pending is not None and pending >= self.max_pending:
            self.requests_shed += 1
            raise _res().RejectedError(
                f"admission queue full ({pending}/{self.max_pending} "
                f"pending); retry in ~{self.flush_deadline_s:.3f}s",
                retry_after_s=self.flush_deadline_s)

    # -- synchronous path ---------------------------------------------------
    def submit(self, x: np.ndarray, *, deadline_s: float | None = None
               ) -> int:
        """Queue one sample (no batch dim).  Returns its request id.

        Ids come from a dedicated monotonic counter, NOT from
        ``requests_served`` (which advances in *chunk* order during
        :meth:`flush` — deriving ids from it let ids collide with
        already-issued ones whenever a flush died mid-way).  An id is
        issued exactly once, forever.

        ``deadline_s`` bounds how long the request may wait in the
        queue: if the next :meth:`flush` starts after the deadline, the
        request is dropped (its output row is ``None``, counted in
        ``requests_expired``) instead of burning a dispatch slot on an
        answer nobody is waiting for.  With ``max_pending`` set, a full
        queue rejects admission with ``RejectedError`` instead of
        growing without bound.

        Thread-safe: queue append and id issue happen under the same
        lock the async worker and :meth:`flush` take, so concurrent
        submitters can neither collide on an id nor corrupt the queue.
        """
        sample = np.asarray(x, dtype=np.float32)
        deadline = self._admit_deadline(deadline_s)
        with self._cv:
            self._shed_locked(len(self._queue))
            self._queue.append((sample, deadline))
            rid = self._next_id
            self._next_id += 1
        return rid

    def flush(self) -> list[np.ndarray]:
        """Run all queued requests; returns outputs in submission order.

        If a chunk's dispatch raises, the failure is re-raised as
        :class:`FlushDispatchError` carrying the already-computed
        partial results, and every *undispatched* request is restored
        to the queue head (submission order preserved) so the next
        ``flush`` serves them — nothing is silently dropped.  The
        failed chunk itself is NOT requeued: a poison request would
        otherwise kill every subsequent flush forever.

        With a :class:`~repro.runtime.resilience.RetryPolicy`
        configured, *transient* chunk failures retry with backoff
        first; only retry-budget exhaustion (the chunk is then recorded
        in ``self.quarantined``) or a non-transient error reaches the
        ``FlushDispatchError`` path.  Requests whose ``deadline_s``
        already passed are dropped up front (``None`` output row,
        ``requests_expired``).
        """
        with self._cv:
            queue, self._queue = self._queue, []
        outs: list[np.ndarray | None] = [None] * len(queue)
        live_pos = list(range(len(queue)))
        if any(d is not None for _, d in queue):
            now = time.monotonic()
            live_pos = [p for p in live_pos
                        if queue[p][1] is None or now < queue[p][1]]
            if len(live_pos) < len(queue):
                with self._cv:
                    self.requests_expired += len(queue) - len(live_pos)
        chunks = list(self._chunks([queue[p][0] for p in live_pos]))
        for ci, (chunk_pos, batch, n_real, bucket) in enumerate(chunks):
            try:
                y = self._guarded_dispatch(batch)
            except Exception as e:          # noqa: BLE001 — rewrapped
                qpos = [live_pos[p] for p in chunk_pos]
                self._note_quarantine(e, n_real)
                tail = sorted(live_pos[p] for c in chunks[ci + 1:]
                              for p in c[0])
                with self._cv:
                    self._queue[:0] = [queue[p] for p in tail]
                raise FlushDispatchError(
                    f"dispatch failed on a chunk of {n_real} request(s) "
                    f"(bucket {bucket}); {len(tail)} undispatched "
                    f"request(s) restored to the queue",
                    partial=outs, failed=qpos,
                    requeued=len(tail)) from e
            for p, row in zip(chunk_pos, y[:n_real]):
                outs[live_pos[p]] = row
            self._count(n_real, bucket)
        return outs

    def _model_run(self, batch):
        """One model dispatch, routed through the supervisor's current
        lane when one is installed (degradation changes the backend,
        bit-for-bit never the outputs — DESIGN §3.3/§3.5)."""
        sup = self._supervisor
        if sup is not None:
            return self.model.run(batch, backend=sup.backend)
        return self.model.run(batch)

    def _guarded_dispatch(self, batch: np.ndarray) -> np.ndarray:
        """Dispatch one host chunk under the resilience ladder: fire the
        injection site, run on the current lane, block to host.  With a
        retry policy, transient failures re-execute with backoff (the
        jitted dispatch is side-effect free on failure); with a
        supervisor, device loss degrades the lane and retries there.
        Unconfigured, this is exactly ``np.asarray(model.run(...))``."""

        def _attempt():
            self._fire("server.dispatch")
            return np.asarray(self._model_run(jnp.asarray(batch)))

        pol, sup = self._retry_policy, self._supervisor
        if pol is None and sup is None:
            return _attempt()
        t0 = time.monotonic()
        y = _res().retry_call(_attempt, policy=pol, supervisor=sup)
        if sup is not None:
            sup.record_latency(time.monotonic() - t0)
        return y

    def _note_quarantine(self, exc: BaseException, n_real: int) -> None:
        """Record a consumed-not-requeued chunk.  Only exhaustion of a
        configured retry budget counts as quarantine; a plain dispatch
        error without a policy keeps PR-6 semantics untouched."""
        if not isinstance(exc, _res().QuarantinedError):
            return
        with self._cv:
            self.requests_quarantined += n_real
            self.quarantined.append({
                "n_requests": n_real, "attempts": exc.attempts,
                "error": repr(exc.__cause__ or exc),
                "t": time.monotonic()})
            del self.quarantined[:-64]      # bounded log

    def serve(self, samples) -> list[np.ndarray]:
        """Convenience: submit + flush a list of single samples."""
        for s in samples:
            self.submit(s)
        return self.flush()

    # -- asynchronous path --------------------------------------------------
    @property
    def async_pending(self) -> int:
        """Requests submitted via :meth:`submit_async` not yet dispatched."""
        with self._cv:
            return len(self._async_queue)

    def submit_async(self, x: np.ndarray, *,
                     deadline_s: float | None = None) -> futures.Future:
        """Queue one sample (no batch dim) on the background flush loop.

        Returns immediately with a :class:`concurrent.futures.Future`
        that resolves to this sample's output row (host ``np.ndarray``)
        once its batch is dispatched — by the ``max_batch`` load trigger
        or the ``flush_deadline_s`` latency trigger, whichever fires
        first.  If the batch dispatch raises, the exception lands on the
        future (``.result()`` re-raises it).  Starts the flush loop if it
        is not running.  Raises ``RuntimeError`` after :meth:`stop_async`
        began (a future that could never resolve must not be issued).

        ``deadline_s`` bounds queue wait: a request still undispatched
        when its deadline passes resolves to
        :class:`~repro.runtime.resilience.DeadlineExceeded` instead of
        occupying a batch slot.  With ``max_pending`` set, a full
        admission queue sheds the request with ``RejectedError``
        (``retry_after_s`` hint) rather than queueing unboundedly.
        """
        fut: futures.Future = futures.Future()
        sample = np.asarray(x, dtype=np.float32)
        deadline = self._admit_deadline(deadline_s)
        with self._cv:
            if self._stopping:
                raise RuntimeError("server is stopping; submit_async "
                                   "rejected (future would never resolve)")
            self._shed_locked(len(self._async_queue))
            if self._worker is None or not self._worker.is_alive():
                self._start_locked()
            self._async_queue.append(_AsyncReq(sample, fut, deadline))
            if self._oldest_t is None:
                self._oldest_t = time.monotonic()
            self._cv.notify_all()
        return fut

    def _cancel_pending_locked(self) -> None:
        for req in self._async_queue:
            req.future.cancel()
        self._async_queue.clear()
        self._oldest_t = None

    def _fail_live_locked(self, exc: BaseException) -> None:
        # crash past the restart budget: every undispatched future gets
        # the WorkerCrashed (already-cancelled ones stay cancelled)
        for req in self._async_queue:
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(exc)
        self._async_queue.clear()
        self._oldest_t = None

    def _loop(self) -> None:
        """Background worker: wait for a trigger, take the whole queue,
        dispatch it bucketed with double-buffered staging."""
        while True:
            # injection site "server.worker": fires BEFORE the queue is
            # taken, so a crash here leaves every pending request queued
            # for the restarted loop (or for _fail_live_locked)
            self._fire("server.worker")
            with self._cv:
                while not self._stopping:
                    if len(self._async_queue) >= self.max_batch:
                        break                      # load trigger
                    if self._oldest_t is not None:
                        wait = (self._oldest_t + self.flush_deadline_s
                                - time.monotonic())
                        if wait <= 0:
                            break                  # latency trigger
                        self._cv.wait(wait)
                    else:
                        self._cv.wait()
                taken = self._async_queue
                self._async_queue = []
                self._oldest_t = None
                stopping = self._stopping
            if taken:
                self._dispatch_async(taken)
            if stopping:
                return

    def _dispatch_async(self, taken) -> None:
        """Run one drained queue: stage batch i+1's host→device transfer
        while batch i computes (double buffering), resolve each batch's
        futures as its results arrive, and propagate a failed dispatch
        into exactly that batch's futures.  With resilience configured
        the chunks route through :meth:`_guarded_dispatch` (retry /
        quarantine / supervisor lane) instead of the overlapped fast
        path — the unconfigured path is exactly the pre-resilience
        code."""
        # drop requests cancelled while queued BEFORE batching — they
        # must neither burn compute nor inflate requests_served (this
        # also moves every surviving future to RUNNING, so a cancel
        # arriving after this point is a no-op).  Deadline-expired
        # requests resolve to DeadlineExceeded here, for the same
        # reason: never burn a batch slot on an abandoned request.
        live = []
        now = time.monotonic()
        expired = 0
        for req in taken:
            if not req.future.set_running_or_notify_cancel():
                continue
            if req.deadline is not None and now >= req.deadline:
                expired += 1
                req.future.set_exception(_res().DeadlineExceeded(
                    "deadline expired before dispatch"))
                continue
            live.append(req)
        if expired:
            with self._cv:
                self.requests_expired += expired
        if not live:
            return
        samples = [r.sample for r in live]
        futs = [r.future for r in live]
        chunks = list(self._chunks(samples))
        if (self._retry_policy is not None or self._supervisor is not None
                or self._injector is not None):
            self._dispatch_chunks_resilient(chunks, futs)
            return
        staged: list = [None] * len(chunks)
        if chunks:                      # stage the first transfer
            staged[0] = _stage(chunks[0][1])
        for i, (chunk_pos, batch, n_real, bucket) in enumerate(chunks):
            try:
                if isinstance(staged[i], Exception):
                    raise staged[i]     # its transfer failed
                y_dev = self.model.run(staged[i])
            except Exception as e:      # noqa: BLE001 — lands on futures
                y_dev, err = None, e
            else:
                err = None
            if i + 1 < len(chunks):     # overlaps with batch i's compute
                staged[i + 1] = _stage(chunks[i + 1][1])
            if err is None:
                try:
                    y = np.asarray(y_dev)   # block on batch i only
                except Exception as e:  # noqa: BLE001
                    err = e
            staged[i] = None            # release batch i's device buffer
            if err is None:
                # account BEFORE resolving: a caller waking up on
                # Future.result() must already see this batch counted
                self._count(n_real, bucket)
            for j, p in enumerate(chunk_pos):
                if err is not None:
                    futs[p].set_exception(err)
                else:
                    futs[p].set_result(y[j])

    def _dispatch_chunks_resilient(self, chunks, futs) -> None:
        """Async dispatch under the resilience ladder: each chunk runs
        through :meth:`_guarded_dispatch` (fire site → current lane →
        block), retries transients, quarantines on budget exhaustion
        (the chunk's futures get the ``QuarantinedError``; later chunks
        are unaffected), and feeds per-chunk latency to the supervisor.
        No double-buffer overlap here — a retried chunk must own its
        dispatch end-to-end."""
        for chunk_pos, batch, n_real, bucket in chunks:
            try:
                y = self._guarded_dispatch(batch)
            except Exception as e:      # noqa: BLE001 — lands on futures
                self._note_quarantine(e, n_real)
                for p in chunk_pos:
                    futs[p].set_exception(e)
                continue
            self._count(n_real, bucket)
            for j, p in enumerate(chunk_pos):
                futs[p].set_result(y[j])


def _stage(batch: np.ndarray):
    """Start the async host→device transfer for a staged batch.  A
    failed transfer is returned, not raised: the dispatch loop fails
    exactly that batch's futures with it."""
    try:
        return jax.device_put(batch)
    except Exception as e:              # noqa: BLE001 — lands on futures
        return e


def codr_serving_stats(cfg, *, n_unique: int = 16, seed: int = 0,
                       reports: list[TensorReport] | None = None) -> dict:
    """Per-decode-token weight HBM traffic under each format (GB).

    When ``reports`` (the :class:`TensorReport` list from a real
    ``codr_compress_params`` / ``api.compile_params`` run) is given,
    bits/weight is **measured** from the model's own tensors.  Without
    it the number is extrapolated from one synthetic 512×512 Gaussian
    matrix — ``stats["source"]`` says which you got, and printers must
    label the synthetic path as an estimate.
    """
    n_active = cfg.active_param_count()
    if reports:
        tot_w = sum(r.n_weights for r in reports)
        bits_pw = sum(r.codr_bits for r in reports) / tot_w
        pack_pw = sum(r.pack_bits for r in reports) / tot_w
        source = "measured"
    else:
        rng = np.random.default_rng(seed)
        w = rng.normal(size=(512, 512)).astype(np.float32) * 0.02
        _, rep = compress_tensor(w, n_unique=n_unique)
        bits_pw = rep["codr_bits"] / w.size
        pack_pw = rep["pack_bits"] / w.size
        source = "synthetic-estimate"
    return {
        "bf16_gb": n_active * 2 / 1e9,
        "int8_gb": n_active * 1 / 1e9,
        "codr_gb": n_active * bits_pw / 8 / 1e9,
        "codr_bits_per_weight": bits_pw,
        "pack_bits_per_weight": pack_pw,
        "source": source,
    }
