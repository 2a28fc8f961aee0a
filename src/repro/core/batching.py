"""Continuous batching: a production decode loop over packed weights.

``ContinuousBatcher`` runs a fixed pool of KV-cache slots (one pooled
cache whose batch axis is the slot axis) and drives every *active* slot
forward with a single jitted ``decode_step`` per iteration:

* **join-on-prefill** — a new request is prefilled on its own (batch-1,
  its exact prompt length) and its cache block-written into a free slot
  (:func:`repro.models.cache.write_slot`); the pooled decode batch never
  stalls behind a long prompt, and in-flight requests never recompile.
* **leave-on-EOS** — a slot retires the moment its request samples
  ``eos_id`` or hits ``max_new_tokens``, freeing the slot for the next
  admission while the rest of the pool keeps decoding.
* **streaming** — :meth:`submit` returns a :class:`GenerationHandle`
  immediately; iterating it yields tokens as they are produced, and
  ``handle.result()`` blocks for the full sequence.

Per-request results are **bit-identical** to a solo decode of the same
prompt on the same params (:meth:`ContinuousBatcher.generate_reference`
is that oracle, sharing the batcher's compiled functions): decode
attention masks every cache position beyond a slot's own ``pos``, so a
neighbour slot's content — or the stale tail a previous tenant left —
contributes exactly 0.0, and XLA's per-row computation does not mix
rows.  The slot state machine and streaming contract are documented in
``docs/DESIGN.md`` §3.4.

The async chassis (condition-variable worker, lazy start, stop/drain/
restart, exception isolation) is :class:`repro.core.serving
.AsyncWorkerLoop`, shared with ``CodrBatchServer``.
"""
from __future__ import annotations

import collections
import dataclasses
import queue as queue_mod
import time
from concurrent import futures

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.serving import AsyncWorkerLoop
from repro.runtime import tracing

_DONE = object()                    # stream sentinel: generation finished
EXPERT_LOG = 8192                   # programs whose expert counts are kept


class GenerationHandle:
    """Streaming handle for one request.

    * iterate it (``for tok in handle``) to stream tokens as the pool
      produces them — the iterator ends at EOS/max-tokens and re-raises
      a generation failure;
    * ``handle.result(timeout)`` blocks for the full token list;
    * ``handle.finish_reason`` is ``"eos"``, ``"length"``,
      ``"cancelled"`` or ``"error"`` once finished.

    Tokens are plain Python ints.  When the batcher was built with
    ``record_logits=True``, ``handle.logits`` holds one float32 vocab
    row per emitted token (the bit-identity witness).
    """

    def __init__(self, rid: int, prompt_len: int, max_new_tokens: int):
        self.rid = rid
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.finish_reason: str | None = None
        self.future: futures.Future = futures.Future()
        self.logits: list[np.ndarray] = []
        self._tokens: list[int] = []
        self._stream: queue_mod.SimpleQueue = queue_mod.SimpleQueue()

    # -- worker side --------------------------------------------------------
    def _emit(self, tok: int, logits_row: np.ndarray | None = None) -> None:
        self._tokens.append(tok)
        if logits_row is not None:
            self.logits.append(logits_row)
        self._stream.put(tok)

    def _finish(self, reason: str) -> None:
        self.finish_reason = reason
        self.future.set_result(list(self._tokens))
        self._stream.put(_DONE)

    def _fail(self, exc: BaseException, reason: str = "error") -> None:
        self.finish_reason = reason
        self.future.set_exception(exc)
        self._stream.put(exc)

    # -- caller side --------------------------------------------------------
    def __iter__(self):
        while True:
            item = self._stream.get()
            if item is _DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def result(self, timeout: float | None = None) -> list[int]:
        """Block until generation finishes; returns all emitted tokens."""
        return self.future.result(timeout)

    @property
    def tokens(self) -> list[int]:
        """Tokens emitted so far (snapshot; may still be growing)."""
        return list(self._tokens)

    def done(self) -> bool:
        return self.future.done()


@dataclasses.dataclass
class _Slot:
    """One occupied pool slot (ACTIVE state of the slot machine)."""
    handle: GenerationHandle
    eos_id: int | None
    last_tok: int                   # token fed to the next decode step
    pos: int                        # cache position that step writes
    n_gen: int                      # tokens emitted so far
    deadline: float | None = None   # absolute monotonic deadline


@dataclasses.dataclass
class _Pending:
    """A submitted request waiting for a free slot (QUEUED state)."""
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: int | None
    handle: GenerationHandle
    deadline: float | None = None   # absolute monotonic deadline
    queued_ns: int | None = None    # submit time, when spans are recorded


def _outputs(out):
    """``(logits, cache, experts touched)`` of a prefill or decode step:
    the count only a MoE model's programs return, None for the rest."""
    return (*out, None)[:3]


def _codr_row_blocks(params):
    """``codr_matmul``'s ``row_blocks`` (how many times an ``m``-row call
    decodes each packed weight block) when a projection of ``params``
    runs on that kernel, else None."""
    from repro.core.codr_linear import PackedLinear
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda leaf: isinstance(leaf, PackedLinear))
    if not any(isinstance(leaf, PackedLinear)
               and leaf.backend == "codr_matmul" for leaf in leaves):
        return None
    from repro.kernels.codr_matmul.kernel import row_blocks
    return row_blocks


class ContinuousBatcher(AsyncWorkerLoop):
    """Slot-pooled continuous-batching decode loop over an LM.

    ``params`` may be a raw params pytree or an
    :class:`repro.core.api.CompiledParams` (packed weights; its
    ``.params`` pytree is served through the backend registry exactly as
    in ``launch/serve.py --codr``).  Decoder-only families only — the
    encoder-decoder cache (per-request encoder output) has no pooled
    form here.

    The worker admits up to ``prefill_per_step`` queued requests per
    iteration (each prefilled at its own prompt length, outside the
    decode batch), then advances every active slot with ONE pooled
    ``decode_step`` whose per-slot positions ride in a ``(n_slots,)``
    vector.  ``join_deadline_s > 0`` lets a partially-filled pool wait
    that long after an admission for co-riders before decoding resumes
    (a latency/throughput knob mirroring ``CodrBatchServer``'s
    ``flush_deadline_s``).

    A failed *prefill* fails only its own request's handle; a failed
    pooled *decode step* fails the handles of exactly the slots that
    were active in it.  The worker survives both and keeps serving.
    """

    _thread_name = "codr-continuous-batcher"

    def __init__(self, params, cfg, *, n_slots: int = 4, max_len: int = 128,
                 eos_id: int | None = None, prefill_per_step: int = 1,
                 join_deadline_s: float = 0.0, record_logits: bool = False,
                 max_pending: int | None = None,
                 kv_dtype: str = "bf16", kv_page_size: int | None = None,
                 kv_pages: int | None = None):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if max_len < 2:
            raise ValueError("max_len must be >= 2")
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        if kv_dtype == "int8" and kv_page_size is None:
            kv_page_size = 16            # int8 storage is always paged
        if max_pending is not None and max_pending < 1:
            raise ValueError("max_pending must be >= 1 (or None)")
        if cfg.family == "encdec" or cfg.frontend:
            raise NotImplementedError(
                "ContinuousBatcher supports decoder-only LM configs "
                f"(got family={cfg.family!r}, frontend={cfg.frontend!r})")
        super().__init__()
        from repro.models import get_model          # lazy: core → models
        from repro.models import cache as cache_mod
        from repro.models import lm
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.prefill_per_step = max(1, prefill_per_step)
        self.join_deadline_s = join_deadline_s
        self.record_logits = record_logits
        self.max_pending = max_pending      # bounded admission (None=∞)
        # CompiledParams duck-typing: serve from its packed pytree
        self._params = getattr(params, "params", params)
        self._row_blocks = _codr_row_blocks(self._params)
        self._api = get_model(cfg)
        self._cache_mod = cache_mod
        api = self._api

        # named for what they run: the trace shows jit_prefill(...),
        # jit_decode_step(...) and jit_write_slot(...).  A MoE model's
        # also return its count of (layer, expert) pairs given rows
        # (``_outputs``); a dense model's programs stay as they were.
        count = lm.has_experts(cfg)

        def prefill(p, t):
            return api.prefill(p, {"tokens": t}, cfg, count_experts=count)

        def decode_step(p, pool, tok, pos):
            return api.decode_step(p, pool, tok, pos, cfg,
                                   count_experts=count)

        self._prefill_fn = jax.jit(prefill)
        self._step_fn = jax.jit(decode_step)
        if kv_page_size is not None:
            # paged KV: pool of fixed-size pages + per-slot page tables
            # (docs/DESIGN.md §2.2).  The page table lives host-side
            # (self._kv_table) — admission allocates, retirement frees
            # by repointing rows at the scratch page — and is pushed
            # into the device pool before every decode step.
            self._paged = cache_mod.PagedSpec(
                page_size=kv_page_size, max_len=max_len, n_slots=n_slots,
                kv_dtype=kv_dtype, n_pages=kv_pages)
            self._paged.total_pages     # validate geometry up front
            self._page_pool = cache_mod.PagePool(self._paged)
            self._slot_pages: list[list[int] | None] = [None] * n_slots  # guarded-by: _cv
            self._kv_table = np.zeros((n_slots, self._paged.max_pages),  # guarded-by: _cv
                                      np.int32)

            def write_slot(pool, c, slot, pages):
                return cache_mod.write_slot_paged(pool, c, slot, pages)

            self._write_fn = jax.jit(write_slot)
            self._pool = self._api.init_cache(cfg, n_slots, max_len,
                                              paged=self._paged)
        else:
            self._paged = None
            # slot axis per cache leaf, discovered structurally (stacked
            # scan-carry leaves lead with n_periods, prologue leaves
            # with batch) — no arrays materialized
            self._axes = cache_mod.diff_axes(
                jax.eval_shape(lambda: self._api.init_cache(cfg, 1,
                                                            max_len)),
                jax.eval_shape(lambda: self._api.init_cache(cfg, 2,
                                                            max_len)))

            def write_slot(pool, c, slot):
                return cache_mod.write_slot(pool, c, slot, self._axes)

            self._write_fn = jax.jit(write_slot)
            self._pool = self._api.init_cache(cfg, n_slots, max_len)
        self._slots: list[_Slot | None] = [None] * n_slots  # guarded-by: _cv
        self._pending: list[_Pending] = []  # guarded-by: _cv
        self._next_id = 0                   # guarded-by: _cv
        self._abort_active = False          # guarded-by: _cv
        self._last_admit_t: float | None = None   # guarded-by: _cv
        # stats (written by the worker under _cv)
        self.steps_run = 0                  # guarded-by: _cv
        self.prefills_run = 0               # guarded-by: _cv
        self.requests_finished = 0          # guarded-by: _cv
        self.peak_active = 0                # guarded-by: _cv
        self.requests_shed = 0              # guarded-by: _cv
        self.requests_expired = 0           # guarded-by: _cv
        # a MoE model's (layer, expert) pairs given rows, per program:
        # (host time it came back, "prefill" | "step", token rows, count)
        self.experts_touched: collections.deque = collections.deque(
            maxlen=EXPERT_LOG)              # guarded-by: _cv

    # -- submission ---------------------------------------------------------
    def submit(self, prompt, *, max_new_tokens: int = 16,
               eos_id: int | None = None,
               deadline_s: float | None = None) -> GenerationHandle:
        """Queue one prompt (1-D int token array).  Returns immediately
        with a :class:`GenerationHandle`; the worker starts lazily.
        ``eos_id`` overrides the batcher default for this request.

        Admission validates the request against the slot geometry up
        front: the prompt plus its ``max_new_tokens`` headroom must fit
        the pool's ``max_len`` (a request that would overflow its KV
        slot mid-stream is rejected here with a clear ``ValueError``,
        never admitted).  ``deadline_s`` bounds the request's total
        latency — a request still queued (or still generating) when its
        deadline passes fails with ``DeadlineExceeded``
        (``finish_reason == "deadline"``) instead of holding a slot.
        With ``max_pending`` set, a full admission queue sheds with
        ``RejectedError`` rather than growing without bound.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt_len {prompt.size} + max_new_tokens "
                f"{max_new_tokens} = {prompt.size + max_new_tokens} "
                f"exceeds pool max_len {self.max_len}: the request would "
                f"overflow its KV slot mid-stream (shorten the prompt or "
                f"lower max_new_tokens)")
        deadline = None
        if deadline_s is not None:
            if deadline_s <= 0:
                raise ValueError("deadline_s must be > 0 (or None)")
            deadline = time.monotonic() + deadline_s
        queued = tracing.now()
        with self._cv:
            if self._stopping:
                raise RuntimeError(
                    "batcher is stopping; submit rejected (handle would "
                    "never resolve)")
            if (self.max_pending is not None
                    and len(self._pending) >= self.max_pending):
                self.requests_shed += 1
                if queued is not None:
                    tracing.record("batcher.queue", queued,
                                   time.monotonic_ns(),
                                   prompt_len=int(prompt.size),
                                   outcome="shed")
                from repro.runtime.resilience import RejectedError
                raise RejectedError(
                    f"admission queue full ({len(self._pending)}/"
                    f"{self.max_pending} pending); retry once a slot "
                    "frees", retry_after_s=self.join_deadline_s or 0.05)
            handle = GenerationHandle(self._next_id, int(prompt.size),
                                      max_new_tokens)
            self._next_id += 1
            self._pending.append(_Pending(
                prompt, max_new_tokens,
                self.eos_id if eos_id is None else eos_id, handle,
                deadline, queued))
            if self._worker is None or not self._worker.is_alive():
                self._start_locked()
            self._cv.notify_all()
        return handle

    @property
    def active(self) -> int:
        with self._cv:
            return sum(s is not None for s in self._slots)

    @property
    def pending(self) -> int:
        with self._cv:
            return len(self._pending)

    def kv_bytes(self) -> int:
        """Measured bytes of the KV pool as stored — page data + scales
        + tables (paged) or the contiguous slot buffers (dense).  The
        cache-side counterpart of ``CompiledParams.hbm_bytes()``."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(self._pool))

    def lower_decode_step(self) -> jax.stages.Lowered:
        """The pooled decode step lowered for this pool's shapes — what
        ``.compile().as_text()`` inspects (e.g. for the Mosaic kernel's
        ``tpu_custom_call``)."""
        z = jnp.zeros((self.n_slots,), jnp.int32)
        return self._step_fn.lower(self._params, self._pool, z, z)

    # -- paged-KV bookkeeping (all under self._cv) ---------------------------
    def _pages_ok_locked(self) -> bool:
        """Can the head pending request reserve its full page budget?"""
        if self._paged is None or not self._pending:
            return True
        req = self._pending[0]
        need = self._paged.pages_for(req.prompt.size + req.max_new_tokens)
        return self._page_pool.available >= need

    def _release_pages_locked(self, slot_idx: int) -> None:
        """Free a retired/failed slot's pages and repoint its page-table
        row at the scratch page, so the pooled decode step's dead write
        for this now-inactive slot cannot land in a page that a new
        request may already own."""
        if self._paged is None:
            return
        pages = self._slot_pages[slot_idx]
        if pages:
            self._page_pool.free(pages)
        self._slot_pages[slot_idx] = None
        self._kv_table[slot_idx, :] = self._cache_mod.SCRATCH_PAGE

    # -- AsyncWorkerLoop hooks ----------------------------------------------
    def _cancel_pending_locked(self) -> None:
        self._abort_active = True
        for p in self._pending:
            p.handle._fail(futures.CancelledError(), reason="cancelled")
        self._pending.clear()

    def _fail_live_locked(self, exc: BaseException) -> None:
        # worker died past the restart budget: every queued AND active
        # handle gets the failure — result() and the stream iterator
        # must never hang on a dead loop, even mid-generation
        for p in self._pending:
            if not p.handle.done():
                p.handle._fail(exc)
        self._pending.clear()
        for i, s in enumerate(self._slots):
            if s is not None:
                self._slots[i] = None
                self._release_pages_locked(i)
                if not s.handle.done():
                    s.handle._fail(exc)

    def _guarded(self, fn):
        """Run one dispatch under the retry/supervisor ladder; exactly
        ``fn()`` when neither is configured."""
        pol, sup = self._retry_policy, self._supervisor
        if pol is None and sup is None:
            return fn()
        from repro.runtime import resilience
        return resilience.retry_call(fn, policy=pol, supervisor=sup)

    @staticmethod
    def _queued(req: _Pending, **attrs) -> None:
        """The ``batcher.queue`` span of ``req``: submit to now."""
        if req.queued_ns is not None:
            tracing.record("batcher.queue", req.queued_ns,
                           time.monotonic_ns(), rid=req.handle.rid,
                           prompt_len=int(req.prompt.size), **attrs)

    def _loop(self) -> None:
        with self._cv:
            self._abort_active = False
        while True:
            # injection site "batcher.worker": fires with no queue or
            # slot state held mid-mutation, so a crash here restarts
            # cleanly with every pending request and active slot intact
            self._fire("batcher.worker")
            with self._cv:
                while not self._stopping:
                    has_free = any(s is None for s in self._slots)
                    n_active = sum(s is not None for s in self._slots)
                    if (self._pending and has_free
                            and self._pages_ok_locked()):
                        break                       # admission work
                    if n_active:
                        # join deadline: a partially-filled pool lingers
                        # briefly after an admission so co-riders can
                        # join the decode batch
                        if (self.join_deadline_s > 0 and has_free
                                and self._last_admit_t is not None):
                            wait = (self._last_admit_t
                                    + self.join_deadline_s
                                    - time.monotonic())
                            if wait > 0:
                                self._cv.wait(wait)
                                continue
                        break                       # decode work
                    self._cv.wait()
                if self._stopping:
                    if self._abort_active:
                        for i, s in enumerate(self._slots):
                            if s is not None:
                                s.handle._fail(futures.CancelledError(),
                                               reason="cancelled")
                                self._slots[i] = None
                                self._release_pages_locked(i)
                        return
                    if (not self._pending
                            and not any(s is not None for s in self._slots)):
                        return                      # drained
                admits: list[tuple[int, _Pending, np.ndarray | None]] = []
                for _ in range(self.prefill_per_step):
                    free = [i for i, s in enumerate(self._slots)
                            if s is None]
                    if not free or not self._pending:
                        break
                    if not self._pages_ok_locked():
                        break      # head request waits for page frees
                    req = self._pending.pop(0)
                    if (req.deadline is not None
                            and time.monotonic() >= req.deadline):
                        # expired while queued: never burn a prefill on
                        # a request nobody is waiting for
                        self.requests_expired += 1
                        self._queued(req, outcome="expired")
                        from repro.runtime.resilience import \
                            DeadlineExceeded
                        req.handle._fail(DeadlineExceeded(
                            "deadline expired before admission"),
                            reason="deadline")
                        continue
                    # reserve the slot (and, paged, its whole page
                    # budget — all-or-nothing, so a request can never
                    # run out of pages mid-stream) under the lock;
                    # prefill happens outside it
                    self._queued(req)
                    kv_row = None
                    if self._paged is not None:
                        need = self._paged.pages_for(
                            req.prompt.size + req.max_new_tokens)
                        pages = self._page_pool.alloc(need)
                        assert pages is not None  # _pages_ok_locked held
                        self._slot_pages[free[0]] = pages
                        kv_row = np.full((self._paged.max_pages,),
                                         self._cache_mod.SCRATCH_PAGE,
                                         np.int32)
                        kv_row[:need] = pages
                        self._kv_table[free[0]] = kv_row
                    self._slots[free[0]] = _Slot(
                        req.handle, req.eos_id, last_tok=-1,
                        pos=-1, n_gen=0, deadline=req.deadline)
                    admits.append((free[0], req, kv_row))
            for slot_idx, req, kv_row in admits:
                self._admit(slot_idx, req, kv_row)
            self._decode_active()

    # -- worker internals ---------------------------------------------------
    def _admit(self, slot_idx: int, req: _Pending,
               kv_row: np.ndarray | None = None) -> None:
        """Prefill one request and install it in its reserved slot.  A
        prefill failure (after any configured retries — re-running the
        prefill + slot write is idempotent) releases the slot and fails
        only this handle.  ``kv_row`` is the page-table row built while
        the slot was reserved under ``_cv`` — passed in so the prefill
        never reads ``self._kv_table`` outside the lock."""

        def _attempt():
            self._fire("batcher.prefill")
            logits, cache, touched = _outputs(self._prefill_fn(
                self._params, jnp.asarray(req.prompt[None, :])))
            if self._paged is not None:
                self._pool = self._write_fn(
                    self._pool, cache, jnp.int32(slot_idx),
                    jnp.asarray(kv_row))
            else:
                self._pool = self._write_fn(self._pool, cache,
                                            jnp.int32(slot_idx))
            return np.asarray(logits, np.float32).reshape(-1), touched

        passes = ({} if self._row_blocks is None else
                  {"weight_passes": self._row_blocks(int(req.prompt.size))})
        with tracing.span("batcher.prefill", rid=req.handle.rid,
                          prompt_len=int(req.prompt.size), **passes) as sp:
            try:
                row, touched = self._guarded(_attempt)
            except Exception as e:  # noqa: BLE001 — lands on the handle
                sp.set(error=type(e).__name__)
                with self._cv:
                    self._slots[slot_idx] = None
                    self._release_pages_locked(slot_idx)
                req.handle._fail(e)
                return
            self._count_experts(sp, "prefill", int(req.prompt.size), touched)
            tok = int(np.argmax(row))
            with self._cv:
                slot = self._slots[slot_idx]
                slot.last_tok = tok
                slot.pos = int(req.prompt.size)
                slot.n_gen = 1
                self.prefills_run += 1
                self._last_admit_t = time.monotonic()
                n_active = sum(s is not None for s in self._slots)
                self.peak_active = max(self.peak_active, n_active)
            req.handle._emit(tok, row if self.record_logits else None)
        self._maybe_retire(slot_idx, tok)

    def _decode_active(self) -> None:
        """One pooled decode step over every active slot: a
        ``batcher.step`` span whose children are the page-table and
        input push, the dispatch, the wait for the device, the logits
        fetch and the host's sampling and delivery."""
        with tracing.span("batcher.step") as step:
            with self._cv:
                # deadline sweep: a slot whose request expired mid-stream
                # retires NOW — it must not hold a slot for tokens nobody
                # will read
                expired = [(i, s) for i, s in enumerate(self._slots)
                           if s is not None and s.deadline is not None
                           and time.monotonic() >= s.deadline]
                for i, s in expired:
                    self._slots[i] = None
                    self._release_pages_locked(i)
                    self.requests_finished += 1
                    self.requests_expired += 1
                if expired:
                    from repro.runtime.resilience import DeadlineExceeded
                    for _, s in expired:
                        s.handle._fail(DeadlineExceeded(
                            f"deadline expired after {s.n_gen} token(s)"),
                            reason="deadline")
                    self._cv.notify_all()
                active = [(i, s) for i, s in enumerate(self._slots)
                          if s is not None]
                kv_table = (self._kv_table.copy() if self._paged is not None
                            else None)
            if not active:
                step.discard()
                return
            step.set(n_active=len(active))
            toks = np.zeros((self.n_slots,), np.int32)
            poss = np.zeros((self.n_slots,), np.int32)
            for i, s in active:
                toks[i] = s.last_tok
                poss[i] = s.pos

            def _attempt():
                # retry-safe: self._pool is only replaced on success, so a
                # failed step recomputes from identical state → identical
                # bits on the retry (the pooled step is deterministic)
                pool = self._pool
                with tracing.span("batcher.push"):
                    if kv_table is not None:
                        # push the authoritative host page table into the
                        # device pool: retired slots now point at
                        # scratch, fresh admits at their reserved pages
                        pool = self._cache_mod.set_tables(pool, kv_table)
                    toks_d, poss_d = jnp.asarray(toks), jnp.asarray(poss)
                with tracing.span("batcher.dispatch"):
                    self._fire("batcher.decode")
                    logits, pool, touched = _outputs(self._step_fn(
                        self._params, pool, toks_d, poss_d))
                with tracing.span("batcher.wait"):
                    jax.block_until_ready(logits)
                with tracing.span("batcher.fetch"):
                    rows = np.asarray(logits, np.float32)
                return rows, pool, touched

            t0 = time.monotonic()
            try:
                rows, self._pool, touched = self._guarded(_attempt)
            except Exception as e:  # noqa: BLE001 — exactly this batch
                step.set(error=type(e).__name__)
                with self._cv:
                    for i, s in active:
                        self._slots[i] = None
                        self._release_pages_locked(i)
                        self.requests_finished += 1
                    for _, s in active:
                        s.handle._fail(e)
                return
            sup = self._supervisor
            if sup is not None:
                sup.record_latency(time.monotonic() - t0)
            self._count_experts(step, "step", self.n_slots, touched)
            with self._cv:
                self.steps_run += 1
            with tracing.span("batcher.sample"):
                for i, s in active:
                    tok = int(np.argmax(rows[i]))
                    s.pos += 1
                    s.n_gen += 1
                    s.last_tok = tok
                    s.handle._emit(tok, rows[i].copy() if self.record_logits
                                   else None)
                    self._maybe_retire(i, tok)

    def _count_experts(self, span, kind: str, rows: int, touched) -> None:
        """Record a MoE program's experts-given-rows count (None for a
        model without experts) on its span and in ``experts_touched``."""
        if touched is None:
            return
        n = int(touched)
        span.set(experts_touched=n)
        with self._cv:
            self.experts_touched.append((time.monotonic(), kind, rows, n))

    def _maybe_retire(self, slot_idx: int, tok: int) -> None:
        with self._cv:
            s = self._slots[slot_idx]
            if s is None:
                return
            reason = None
            if s.eos_id is not None and tok == s.eos_id:
                reason = "eos"
            elif s.n_gen >= s.handle.max_new_tokens:
                reason = "length"
            if reason is None:
                return
            self._slots[slot_idx] = None        # slot → FREE
            self._release_pages_locked(slot_idx)
            self.requests_finished += 1
            self._cv.notify_all()
        s.handle._finish(reason)

    # -- solo oracle --------------------------------------------------------
    def generate_reference(self, prompt, *, max_new_tokens: int = 16,
                           eos_id: int | None = None,
                           record_logits: bool = False):
        """Solo decode of ``prompt``: a fresh ``n_slots`` pool with only
        slot 0 active, driven by the SAME compiled prefill/decode
        functions the batcher uses.  This is the bit-identity oracle —
        any pooled run of the same request must emit exactly these
        tokens (and, with ``record_logits``, these logits bits).
        Returns ``(tokens, logits_rows)``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError("prompt + max_new_tokens exceeds max_len")
        eos = self.eos_id if eos_id is None else eos_id
        pool = self._api.init_cache(self.cfg, self.n_slots, self.max_len,
                                    paged=self._paged)
        logits, cache, _ = _outputs(self._prefill_fn(
            self._params, jnp.asarray(prompt[None, :])))
        if self._paged is not None:
            # deterministic solo allocation: the first pages after
            # scratch.  Physical page ids never enter the math (pages
            # are slot-private, scales per-page), so the pooled run is
            # bit-identical whatever ids its allocator happened to pick.
            need = self._paged.pages_for(prompt.size + max_new_tokens)
            row = np.full((self._paged.max_pages,),
                          self._cache_mod.SCRATCH_PAGE, np.int32)
            row[:need] = np.arange(1, need + 1)
            pool = self._write_fn(pool, cache, jnp.int32(0),
                                  jnp.asarray(row))
        else:
            pool = self._write_fn(pool, cache, jnp.int32(0))
        row = np.asarray(logits, np.float32).reshape(-1)
        toks: list[int] = []
        rows: list[np.ndarray] = []
        tok, pos = int(np.argmax(row)), int(prompt.size)
        toks.append(tok)
        if record_logits:
            rows.append(row)
        while len(toks) < max_new_tokens and tok != eos:
            tvec = np.zeros((self.n_slots,), np.int32)
            pvec = np.zeros((self.n_slots,), np.int32)
            tvec[0], pvec[0] = tok, pos
            logits, pool, _ = _outputs(self._step_fn(
                self._params, pool, jnp.asarray(tvec), jnp.asarray(pvec)))
            r = np.asarray(logits, np.float32)[0]
            tok, pos = int(np.argmax(r)), pos + 1
            toks.append(tok)
            if record_logits:
                rows.append(r.copy())
        return toks, rows

    def replay_logits(self, prompt, tokens) -> np.ndarray:
        """Teacher-forced replay: run ``prompt`` then feed the given
        ``tokens`` verbatim (no argmax feedback), returning the
        ``(len(tokens), vocab)`` float32 logits the pipeline produced
        at each step.

        This is the differential-check primitive for lossy KV modes:
        free-running int8 greedy decode legitimately diverges from the
        dense reference after a few near-tied steps, but the *per-step*
        logits under the same forced token stream must stay within the
        int8 quantization floor of the dense run — so ``--check`` and
        the tier-1 differential tests compare ``replay_logits`` rows
        instead of token strings.  Row 0 is the prefill logits row
        (dense compute, paged caches untouched), so it is bit-exact
        across KV modes by construction."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tokens = [int(t) for t in tokens]
        if not tokens:
            return np.zeros((0, self.cfg.vocab_size), np.float32)
        if prompt.size + len(tokens) > self.max_len:
            raise ValueError("prompt + replay tokens exceed max_len")
        pool = self._api.init_cache(self.cfg, self.n_slots, self.max_len,
                                    paged=self._paged)
        logits, cache, _ = _outputs(self._prefill_fn(
            self._params, jnp.asarray(prompt[None, :])))
        if self._paged is not None:
            need = self._paged.pages_for(prompt.size + len(tokens))
            row = np.full((self._paged.max_pages,),
                          self._cache_mod.SCRATCH_PAGE, np.int32)
            row[:need] = np.arange(1, need + 1)
            pool = self._write_fn(pool, cache, jnp.int32(0),
                                  jnp.asarray(row))
        else:
            pool = self._write_fn(pool, cache, jnp.int32(0))
        rows = [np.asarray(logits, np.float32).reshape(-1)]
        pos = int(prompt.size)
        for tok in tokens[:-1]:
            tvec = np.zeros((self.n_slots,), np.int32)
            pvec = np.zeros((self.n_slots,), np.int32)
            tvec[0], pvec[0] = tok, pos
            logits, pool, _ = _outputs(self._step_fn(
                self._params, pool, jnp.asarray(tvec), jnp.asarray(pvec)))
            rows.append(np.asarray(logits, np.float32)[0].copy())
            pos += 1
        return np.stack(rows) if rows else np.zeros((0, 0), np.float32)
