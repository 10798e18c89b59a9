"""LM serving: a deployment around one continuous-batching engine.

The reference serves models through generic deployments plus the
``serve.batch`` request coalescer (python/ray/serve/batching.py:279;
replica loop serve/_private/replica.py:250). Here the coalescer is
TPU-shaped:

  - :class:`ContinuousBatcher` — the engine: callers block in ``submit``,
    one thread admits each into a free slot of ``max_slots`` and decodes
    every occupied slot together, ``steps_per_iter`` tokens an iteration,
    in ONE compiled program over a paged KV pool. On a TPU the batch
    dimension is nearly free (MXU width), so sharing decode iterations is
    the difference between 1x and Nx decode throughput under load. A
    prompt is prefilled in chunks of ``pad_multiple`` positions, each in
    the same program as one decode token-step of the live rows (the
    model's ``mixed_step``: the chunk's matmuls are compute-bound, so the
    decode rows' weight stream is the chunk's; a model that keeps a state
    a slot is handed the row's index too, and its chunk carries on from
    the slot's entry); a model that offers none gets a prefill program per
    prompt bucket while the loop stands. What an iteration's token-steps
    attend over is summed once an iteration, at assembly, on both paths.
  - :class:`LLMServer` — the deployment class: holds the parameters on the
    device, owns the engine, and answers requests and ``stats()``.

Requests carry token ids (``{"tokens": [...]}``) or plain text
(``{"text": ...}``, byte-level fallback tokenizer) — the deployment is
model-complete without shipping a tokenizer dependency.
"""

from __future__ import annotations

import copy
import statistics
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

from ..utils import faults, timeline, tracing
from ..utils.profiling import Flight, phase
from .deployment import deployment

# the engine thread is in exactly one of these at every instant (see
# ContinuousBatcher._loop). "disassemble" reads 0 since the programs write
# the KV in place; readers index the phases by name, so the name stays
ENGINE_PHASES = ("idle_wait", "gate", "prefill", "assemble",
                 "step_dispatch", "step_wait", "emit", "disassemble")
# an iteration stalled when its wall seconds pass this many times the median
# of the last STALL_HISTORY iterations'; the newest STALL_ROWS are kept
STALL_FACTOR, STALL_HISTORY, STALL_ROWS = 4.0, 32, 16


class _Pending:
    __slots__ = ("item", "event", "result", "error", "trace",
                 "t_submit", "t_admit", "t_first", "t_done")

    def __init__(self, item):
        self.item = item
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        # the engine's stamps (time.time()) and the submitting thread's
        # trace context
        self.trace = None
        self.t_submit = self.t_admit = self.t_first = self.t_done = 0.0


def _bytes_tokenize(text: str, vocab_size: int) -> List[int]:
    """Byte-level fallback: utf-8 bytes offset past the special range."""
    return [2 + (b % (vocab_size - 2)) for b in text.encode()]


class ContinuousBatcher:
    """Decode-step-granular request scheduler (continuous batching).

    Requests join and leave at decode-step granularity over a fixed slot
    table (the vLLM/Orca iteration-level scheduling idea, TPU-shaped), so
    under streaming arrivals no request waits for a batch of strangers to
    finish and no slot idles on a retired row:

      - a new request is PREFILLED into a free slot the moment one exists:
        chunk by chunk beside the live rows' decode steps where the model
        offers ``mixed_step`` (``_iterate_mixed``; the row is idle in the
        decode half until its last chunk has run), else by a per-bucket
        compiled prefill that writes its prompt's KV at positions [0, len)
        while the loop stands (``_iterate``). The engine chooses by what
        the model offers, nothing else;
      - every engine iteration runs ONE decode program over all occupied
        slots (static [max_slots] shape, each row at its own position and
        length);
      - a row that reaches its request's token budget retires immediately
        and its slot admits the next queued request at the very next step.

    Per-row positions also make mixed-length batches EXACT: each row
    attends only to its own true history with its own rope phases.

    KV memory is PAGED: the cache is one resident pool of pages on the
    device (:class:`~.kv_cache.KVPagePool`: K and V as
    ``[L, Hkv, P, page_tokens, Dh]``, allocated once by the engine thread)
    addressed through a block table. Each admitted request reserves the
    page ids of its own lifetime (prompt + budget, page aligned); prefill
    scatters the prompt's K and V into those pages, and every iteration
    runs ONE compiled decode program, the same for the engine's lifetime,
    that attends through the table (ops/paged_attention.py) and writes one
    position a live row, all on the donated pool: nothing copies KV between
    iterations, and the host's part of an iteration is the table and the
    lengths of the live slots. ``_retire`` returns the slot's pages, so the
    pool's *pages* track live requests while its bytes stay constant; pool
    exhaustion defers admission (backpressure) instead of OOMing.
    """

    def __init__(self, params, cfg, max_slots: int = 8,
                 max_new_tokens: int = 32, temperature: float = 0.0,
                 pad_multiple: int = 64, seed: int = 0,
                 steps_per_iter: int = 8,
                 kv_page_tokens: Optional[int] = None,
                 kv_pool_bytes: Optional[int] = None):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..config import global_config
        from ..models import serving_model
        from .kv_cache import KVPagePool

        self._jax, self._jnp, self._np = jax, jnp, np
        # the configuration's model: parameters, prefill, the decode step
        # and what a token leaves in the cache are its to say
        self._model = model = serving_model(cfg)
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.pad_multiple = pad_multiple
        # scheduling quantum: each engine iteration decodes K tokens for
        # every occupied row inside ONE compiled lax.scan — per-step
        # Python dispatch would otherwise eat the step-granularity win
        # (K amortizes dispatch K-fold while arrivals still join within K
        # steps and finished rows retire within K steps)
        self.steps_per_iter = max(1, min(steps_per_iter, max_new_tokens))
        self._key = jax.random.PRNGKey(seed)
        gcfg = global_config()
        self.kv_pool = KVPagePool(
            cfg, max_slots=max_slots,
            page_tokens=kv_page_tokens or gcfg.kv_page_tokens,
            pool_bytes=kv_pool_bytes if kv_pool_bytes is not None
            else gcfg.serve_kv_pool_bytes)
        # the pool's arrays, the engine thread's between programs
        # (allocated at its first admission, donated to each)
        self._pool: Optional[Dict[str, Any]] = None
        self._prefill_cache: Dict[Any, Any] = {}  # bucket -> fn
        # buckets whose program attends in the flash kernel
        self._prefill_kernel: set = set()

        def _sample(logits, key):
            if self.temperature > 0:
                return jax.random.categorical(key, logits / self.temperature)
            return jnp.argmax(logits, axis=-1)

        K = self.steps_per_iter

        self._sample = _sample

        def paged_step_fn(params, pool, last, offsets, table, key):
            # a slot with nothing in its cache is idle: it reads nothing
            # and its writes go to the sink its table row points at
            live = offsets > 0

            def body(carry, t):
                pool, last, key = carry
                key, sub = jax.random.split(key)
                logits, pool, counts = model.paged_decode(
                    params, last, pool, offsets + t,
                    jnp.where(live, offsets + t, 0), table, cfg)
                with jax.named_scope("head_sample"):
                    nxt = _sample(logits, sub)
                return (pool, nxt, key), (nxt, counts)

            (pool, _, _), (toks, counts) = jax.lax.scan(
                body, (pool, last, key), jnp.arange(K))
            # toks [K, B]; what the model counted of its step (small
            # arrays, for the dense decoder none), summed over the K steps
            return pool, toks, jax.tree.map(lambda c: c.sum(0), counts)

        # the one decode program: its shapes are the constructor's
        # (max_slots, the table's width, the pool's size), whichever rows
        # are live and however long they are
        self._paged_step = jax.jit(paged_step_fn, donate_argnums=(1,))

        # where the model offers ``mixed_step``, a prompt is prefilled in
        # chunks of ``_chunk`` positions (the bucket step in whole pages),
        # each in the same program as one decode token-step of the live
        # rows: ONE program, whichever chunk of whichever prompt it carries
        self._mixed = hasattr(model, "mixed_step")
        page = self.kv_pool.page_tokens
        self._chunk = -(-pad_multiple // page) * page
        if self._mixed:
            self._mixed_step = self._mixed_step_program()
            if model.prefill_takes_kernel(cfg, self._chunk):
                self._prefill_kernel.add(self._chunk)
        # rows with chunks to go, oldest first; each row's prompt padded to
        # whole chunks with its true length, and how many chunks are done
        self._prefilling: List[int] = []
        self._slot_prompt: List[Any] = [None] * max_slots
        self._slot_chunks = np.zeros(max_slots, np.int32)

        # slot state (host side)
        self._slot_pending: List[Optional[_Pending]] = [None] * max_slots
        self._slot_offset = np.zeros(max_slots, np.int32)
        self._slot_last = np.ones(max_slots, np.int32)
        self._slot_out: List[List[int]] = [[] for _ in range(max_slots)]
        self._slot_budget = np.zeros(max_slots, np.int32)
        self.kv_backpressure = 0  # admissions deferred on pool exhaustion

        self._q: List[_Pending] = []
        self._cond = threading.Condition()
        self._stop = False
        self.steps = 0  # decode steps executed (the "batches" analog)
        # what the engine measures of itself, cumulative since it started.
        # The engine thread is the only writer of these three; it publishes
        # a copy once per iteration (one reference assignment), and that
        # copy is all engine_stats() reads
        # [wall_s, cpu_s, starved_s] a phase; the third is the part of the
        # wall seconds in which nothing this thread dispatched was unread
        self._phase = {name: [0.0, 0.0, 0.0] for name in ENGINE_PHASES}
        self._flight = Flight()
        self._walls: deque = deque(maxlen=STALL_HISTORY)
        self._stalls: deque = deque(maxlen=STALL_ROWS)
        self._counts = {"iterations": 0, "slab_positions": 0,
                        "live_positions": 0, "admitted": 0,
                        "prefill_positions": 0,
                        "prefill_kernel_positions": 0,
                        "mixed_steps": 0, "chunk_positions_live": 0}
        self._recent: deque = deque(maxlen=512)  # (queue_wait_s, prefill_s)
        # what the model's decode step counted of itself (paged_decode's
        # third result, and mixed_step's under names of its own), added up
        # by name: arrays, or nothing
        self._model_counts: Dict[str, Any] = {}
        self._publish()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()

    @property
    def params(self):
        """The model's parameters, passed to every program. Whoever owns the
        replica may take them off the device by setting ``None`` while no
        request is in flight (and put others back before the next one): the
        engine thread is woken and lets its pool go with them."""
        return self._params

    @params.setter
    def params(self, value) -> None:
        self._params = value
        cond = getattr(self, "_cond", None)  # None: the constructor's call
        if value is None and cond is not None:
            with cond:
                cond.notify_all()

    # -- client side ----------------------------------------------------------
    def submit(self, tokens: List[int], timeout: float = 300.0,
               max_new_tokens: Optional[int] = None):
        """Blocking generate. ``max_new_tokens`` may be set PER REQUEST
        (capped by the engine default): with step-granular scheduling a
        short request retires early and frees its slot."""
        budget = self.max_new_tokens if max_new_tokens is None else \
            max(1, min(int(max_new_tokens), self.max_new_tokens))
        p = _Pending((list(tokens), budget))
        # this is the request's own exec thread: its context is the
        # replica's exec span, which the engine's spans hang under
        p.trace = tracing.get_current()
        p.t_submit = time.time()
        with self._cond:
            if self._stop:
                raise RuntimeError("engine closed")
            self._q.append(p)
            self._cond.notify()
        if not p.event.wait(timeout):
            raise TimeoutError("generation timed out")
        if p.error is not None:
            raise p.error
        return p.result

    def close(self) -> None:
        """Stop the engine, failing queued AND slot-resident requests
        promptly with "engine closed" (never leaving a caller to ride out
        its full submit timeout). Slot state belongs to the engine thread,
        so its _stop exit path fails the resident rows; this thread only
        drains the queue."""
        with self._cond:
            self._stop = True
            drained = list(self._q)
            self._q.clear()
            self._cond.notify_all()
        for p in drained:
            p.error = RuntimeError("engine closed")
            p.event.set()

    # -- engine side ----------------------------------------------------------
    def _clip_tokens(self, toks: List[int]) -> List[int]:
        limit = self.cfg.max_seq - self.max_new_tokens
        return toks[-limit:]

    def _bucket_for(self, toks: List[int]) -> int:
        limit = self.cfg.max_seq - self.max_new_tokens
        bucket = max(self.pad_multiple,
                     ((len(toks) + self.pad_multiple - 1)
                      // self.pad_multiple) * self.pad_multiple)
        return min(bucket, limit)

    def _need_tokens(self, p: _Pending) -> int:
        """Page-aligned KV capacity one request needs for its whole
        lifetime: the prefill bucket (whose junk tail must fit) or
        prompt + token budget, whichever is larger."""
        toks, budget = p.item
        toks = self._clip_tokens(list(toks))
        need = max(self._bucket_for(toks), len(toks) + budget)
        return min(self.kv_pool.round_tokens(need), self.cfg.max_seq)

    def _paged_prefill_fn(self, bucket: int):
        """Prefill one prompt of ``bucket`` tokens and scatter what it
        leaves in the cache into the row's pages of the donated pool, and,
        where the model keeps a state a slot (``state_spec``), the row's
        state into the slot's entry: whatever an earlier request left there
        is overwritten whole. Compiled per bucket: the reservation's size
        does not enter, only the table row and the slot's index do."""
        jax, model, cfg = self._jax, self._model, self.cfg
        fn = self._prefill_cache.get(bucket)
        if fn is not None:
            return fn
        page = self.kv_pool.page_tokens
        n_pages = self.kv_pool.pages_for(bucket)
        spec, state_spec = self.kv_pool.spec, self.kv_pool.state_spec

        def prefill(params, pool, tokens, table_row, true_len, key,
                    slot=None):
            logits, row_cache = model.prefill_row(
                params, tokens, cfg, n_pages * page, true_len)
            pages = table_row[:n_pages]

            def scatter(name):  # the row's positions as whole pages
                lead, trail, _ = spec[name]
                at = (slice(None),) * len(lead) + (pages,)
                return pool[name].at[at].set(row_cache[name].reshape(
                    lead + (n_pages, page) + trail))

            def put(name):  # the slot's entry, every layer of it
                at = (slice(None),) * len(state_spec[name][0]) + (slot,)
                return pool[name].at[at].set(row_cache[name])

            pool = {name: (put if name in state_spec else scatter)(name)
                    for name in pool}
            first = self._sample(logits[None], key)[0]
            return pool, first

        fn = jax.jit(prefill, donate_argnums=(1,))
        self._prefill_cache[bucket] = fn
        if model.prefill_takes_kernel(cfg, bucket):
            self._prefill_kernel.add(bucket)
        return fn

    def _admit(self, p: _Pending, row: int) -> None:
        act = faults.fire("serve.admit")
        if act is not None:
            if act.mode == "stall":
                act.sleep()
            else:  # error/drop: fail ONLY this request, engine keeps going
                act.raise_()
        np, jnp = self._np, self._jnp
        toks, budget = p.item
        toks = self._clip_tokens(toks)
        bucket = self._bucket_for(toks)
        arr = np.ones((1, bucket), np.int32)
        arr[0, : len(toks)] = toks  # right-pad junk is invisible: the
        # per-row mask stops at true_len and decode overwrites those slots
        self._key, sub = self._jax.random.split(self._key)
        if self._pool is None:  # the engine's first admission
            self._pool = self.kv_pool.allocate()
        # the row's pages were reserved by the admit gate
        # and where the model keeps a state a slot, its entry is the row's
        slot = (jnp.int32(row),) if self.kv_pool.state_spec else ()
        self._pool, first = self._paged_prefill_fn(bucket)(
            self.params, self._pool, jnp.asarray(arr),
            jnp.asarray(self.kv_pool.table[row]),
            jnp.int32(len(toks)), sub, *slot)
        # the chip has work from here (the split's two tiny programs and
        # the uploads above do not count as work) until the first token is
        # read back
        self._flight.fill()
        # what the prefill programs computed, and how much of it in a
        # program that holds the kernel
        self._counts["prefill_positions"] += bucket
        if bucket in self._prefill_kernel:
            self._counts["prefill_kernel_positions"] += bucket
        first = int(first)
        self._flight.drain()
        self._slot_pending[row] = p
        self._slot_offset[row] = len(toks)
        self._slot_last[row] = first
        self._slot_out[row] = [first]
        self._slot_budget[row] = budget - 1

    def _retire(self, row: int) -> None:
        p = self._slot_pending[row]
        self._slot_pending[row] = None
        self._slot_offset[row] = 0
        self._slot_last[row] = 1
        # the slot's pages return to the free list and its table row to
        # the sink: a queued request can now reserve them
        self.kv_pool.free(row)
        if p is not None:
            p.result = self._slot_out[row]
            p.t_done = time.time()
            p.event.set()
            self._record_request(p, row)

    def _record_request(self, p: _Pending, row: int) -> None:
        """The request's three spans in the timeline ring, each a child of
        the context ``submit()`` ran under (the replica's exec span), so
        ``rmt trace <id>`` walks one request from the handle into queue,
        prefill and decode. Three spans a request, nothing per iteration."""
        toks = self._clip_tokens(p.item[0])
        extra = {"row": row, "prompt_tokens": len(toks),
                 "bucket": self._bucket_for(toks),
                 "output_tokens": len(p.result)}
        for name, start, end in (
                ("serve.engine.queue", p.t_submit, p.t_admit),
                ("serve.engine.prefill", p.t_admit, p.t_first),
                ("serve.engine.decode", p.t_first, p.t_done)):
            timeline.record_event(
                name, "serve", start, end, extra=extra,
                trace=tracing.child_of(p.trace) if p.trace else None)

    def _admit_gate(self) -> List:
        """Pop admissible queued requests (head-of-line FIFO) into free
        slots, reserving each request's lifetime pages FIRST: a failed
        reserve defers admission (backpressure) until a retiring slot frees
        pages, so decode can never OOM mid-request. Caller holds
        ``_cond``."""
        admits = []
        for row in range(self.max_slots):
            if not self._q:
                break
            if self._slot_pending[row] is not None:
                continue
            p = self._q[0]
            need = self._need_tokens(p)
            if self.kv_pool.pages_for(need) > self.kv_pool.capacity_pages:
                # can never fit even in an empty pool: fail fast instead
                # of backpressuring forever
                self._q.pop(0)
                p.error = RuntimeError(
                    f"request needs {need} KV tokens "
                    f"({self.kv_pool.pages_for(need)} pages) but the pool "
                    f"capacity is {self.kv_pool.capacity_pages} pages")
                p.event.set()
                continue
            if not self.kv_pool.reserve(row, need):
                # pool exhausted: keep FIFO order, admit nothing past the
                # head — pages free at the next retire
                self.kv_backpressure += 1
                try:
                    from ..core import metrics_defs as mdefs
                    mdefs.serve_kv_backpressure().inc()
                except Exception:  # noqa: BLE001
                    pass
                break
            admits.append((self._q.pop(0), row))
        now = time.time()
        for p, _ in admits:
            p.t_admit = now
        return admits

    def _loop(self) -> None:
        """The engine thread. Every instant of it lies in exactly one
        ``phase`` (ENGINE_PHASES), so the phases' wall seconds add up to the
        thread's, and each is a ``rmt.engine.<name>`` span on this thread's
        line of a profiler trace."""
        acc, flight = self._phase, self._flight
        while True:
            with self._cond:
                while (not self._stop and not self._q
                       and all(p is None for p in self._slot_pending)):
                    if self.params is None:
                        # the weights left the device (see ``params``): the
                        # cache is of no use without them and goes too; the
                        # next admission allocates it anew
                        self._pool = None
                    with phase(acc, "idle_wait"):
                        self._cond.wait(timeout=1.0)
                    self._publish()
                if self._stop:
                    # fail slot-resident requests too: close() cannot
                    # touch slot state (it races this thread), so the
                    # exit path owns that cleanup
                    victims = [p for p in self._slot_pending
                               if p is not None]
                    self._slot_pending = [None] * self.max_slots
                    self.kv_pool.free_all()
                    self._pool = None  # the arrays leave the device
                    for p in victims:
                        p.error = RuntimeError("engine closed")
                        p.event.set()
                    return
                with phase(acc, "gate", flight):
                    admits = self._admit_gate()
            rows, steps = 0, self.steps
            try:
                rows = (self._iterate_mixed if self._mixed
                        else self._iterate)(admits)
            except BaseException as e:  # noqa: BLE001 — fail loudly to
                # every parked caller, keep serving
                flight.drain()  # nobody reads what the failed iteration left
                with phase(acc, "emit", flight):
                    with self._cond:
                        victims = ([p for p in self._slot_pending
                                    if p is not None] + self._q)
                        self._slot_pending = [None] * self.max_slots
                        self._q.clear()
                    self._prefilling.clear()
                    self._slot_offset[:] = 0
                    self.kv_pool.free_all()
                    # a program that failed may have consumed the donated
                    # arrays: the next admission allocates anew
                    self._pool = None
                    for p in victims:
                        p.error = e
                        p.event.set()
            self._note_iteration(rows, self.steps - steps)
            self._publish()

    def _note_iteration(self, rows: int, token_steps: int) -> None:
        """Engine thread, at an iteration's end: its wall seconds (the work
        phases' since the gate: the accumulators less the copy published at
        the last iteration's end; ``idle_wait`` is no part of an iteration)
        against the median of the last ``STALL_HISTORY`` iterations'. One
        over ``STALL_FACTOR`` times that is a stall and leaves a row."""
        acc, last = self._phase, self._published
        wall = sum(v[0] - last["phase_s"][k] for k, v in acc.items()
                   if k != "idle_wait")
        if self._walls:
            median = statistics.median(self._walls)
            if wall > STALL_FACTOR * median:
                self._stalls.append({
                    "t_end": time.time(), "wall_s": wall, "median_s": median,
                    **{key: {k: v[i] - last[key][k] for k, v in acc.items()}
                       for i, key in enumerate(
                           ("phase_s", "phase_cpu_s", "starved_s"))},
                    "rows": rows, "token_steps": token_steps})
        self._walls.append(wall)

    def _iterate(self, admits) -> int:
        """One iteration where a prompt is prefilled whole (the model offers
        no ``mixed_step``): a prefill program an admission, each with its
        first token read back while the loop stands, then the K token-steps
        of every live row. Returns the rows it stepped."""
        acc, counts, flight = self._phase, self._counts, self._flight
        for p, row in admits:
            with phase(acc, "prefill", flight,
                       bucket=self._bucket_for(
                           self._clip_tokens(p.item[0])),
                       cap=self.kv_pool.row_tokens(row)):
                try:
                    self._admit(p, row)
                except faults.FaultInjected as e:
                    # injected admit failure takes down ONE
                    # request, not the engine: release the
                    # reservation and keep admitting
                    self.kv_pool.free(row)
                    self._slot_pending[row] = None
                    p.error = e
                    p.event.set()
                    continue
                # the first token exists
                p.t_first = time.time()
                counts["admitted"] += 1
                self._recent.append((p.t_admit - p.t_submit,
                                     p.t_first - p.t_admit))
                if self._slot_budget[row] <= 0:
                    self._retire(row)  # max_new_tokens == 1
        active = [r for r in range(self.max_slots)
                  if self._slot_pending[r] is not None]
        if not active:
            return 0
        toks, stepped = self._dispatch_decode(active)
        with phase(acc, "step_wait", flight):
            # toks [K, B] and the model's counts, in one readback
            toks, stepped = self._jax.device_get((toks, stepped))
            flight.drain()
            self._add_model_counts(stepped)
        with phase(acc, "emit", flight):
            self.steps += self.steps_per_iter
            self._emit(toks, active, {})
        return len(active)

    def _dispatch_decode(self, rows):
        """Assemble and dispatch the K token-steps of the live ``rows`` (the
        one decode program); its tokens [K, B] and the model's counts, still
        on the device."""
        jnp = self._jnp
        acc, counts, flight = self._phase, self._counts, self._flight
        with phase(acc, "assemble", flight, rows=len(rows)):
            sub = self._iteration_key()
            # the host's part is the live slots' lengths and table
            # rows; the KV stays where it is
            last = jnp.asarray(self._slot_last)
            offsets = jnp.asarray(self._slot_offset)
            table = jnp.asarray(self.kv_pool.table)
            # what the step fetches, and how much of it is live
            counts["iterations"] += 1
            self._count_positions(self._slot_offset[rows],
                                  self.steps_per_iter)
        with phase(acc, "step_dispatch", flight):
            self._pool, toks, stepped = self._paged_step(
                self.params, self._pool, last, offsets, table, sub)
            flight.fill()
        return toks, stepped

    def _mixed_step_program(self):
        """One chunk of one row's prompt and one decode token-step of the
        live rows, sampled, on the donated pool (the model's
        ``mixed_step``): ONE program, whichever chunk of whichever prompt
        it carries. The mixed steps of an iteration chain on the device:
        each takes the last one's tokens and the rest of its key, so none
        waits for the host. Where the model keeps a state a slot, the
        program is also handed the prefilling row's index, ``slot`` (as the
        whole-prompt prefill is): the chunk continues that slot's entry."""
        jax, jnp, model, cfg = self._jax, self._jnp, self._model, self.cfg

        def mixed_step(params, pool, chunk_tokens, chunk_pages, chunk_index,
                       chunk_last, first_row, last, offsets, table, key,
                       *slot):
            key, sub = jax.random.split(key)
            logits, pool, counts = model.mixed_step(
                params, pool, chunk_tokens, chunk_pages, chunk_last, last,
                offsets, offsets, table, cfg, chunk_index=chunk_index,
                **({"slot": slot[0]} if slot else {}))
            with jax.named_scope("head_sample"):
                toks = self._sample(logits, sub)
            # the row whose prompt ends in this chunk (``first_row``; -1:
            # none does) is live from the next token-step on, and the
            # chunk's token is its first
            nxt = jnp.where(jnp.arange(last.shape[0]) == first_row,
                            toks[-1], toks[:-1])
            return pool, nxt, key, counts

        return jax.jit(mixed_step, donate_argnums=(1,))

    def _begin_prefill(self, p: _Pending, row: int) -> None:
        """Admit ``p`` into ``row`` as a prefilling row: the host keeps its
        prompt, padded to whole chunks (the junk is invisible: every mask
        stops at the true length and decode overwrites those positions), and
        the mixed steps to come carry it chunk by chunk. Its pages were
        reserved by the admit gate; nothing is dispatched here."""
        act = faults.fire("serve.admit")
        if act is not None:
            if act.mode == "stall":
                act.sleep()
            else:  # error/drop: fail ONLY this request, engine keeps going
                act.raise_()
        np = self._np
        toks, budget = p.item
        toks = self._clip_tokens(toks) or [1]
        arr = np.ones(-(-len(toks) // self._chunk) * self._chunk, np.int32)
        arr[: len(toks)] = toks
        self._slot_pending[row] = p
        self._slot_prompt[row] = (arr, len(toks))
        self._slot_chunks[row] = 0
        # idle in the decode half until its last chunk has run
        self._slot_offset[row] = 0
        self._slot_last[row] = 1
        self._slot_out[row] = []
        self._slot_budget[row] = budget
        self._prefilling.append(row)

    def _iterate_mixed(self, admits) -> int:
        """One iteration where the model offers ``mixed_step``: the chunks
        waiting, oldest row first and at most K of them, each in one program
        with a decode token-step of the live rows, or, where no chunk waits,
        the K token-steps of the decode program; then ONE readback of every
        token the iteration made. Returns the rows it held."""
        np = self._np
        acc, counts, flight = self._phase, self._counts, self._flight
        for p, row in admits:
            with phase(acc, "prefill", flight,
                       cap=self.kv_pool.row_tokens(row)):
                try:
                    self._begin_prefill(p, row)
                except faults.FaultInjected as e:
                    # injected admit failure takes down ONE request, not
                    # the engine: release the reservation, keep admitting
                    self.kv_pool.free(row)
                    p.error = e
                    p.event.set()
        active = [r for r in range(self.max_slots)
                  if self._slot_pending[r] is not None]
        if not active:
            return 0
        if self._prefilling:
            outs, began = self._dispatch_mixed(len(active))
        else:
            # the decode program runs only where no chunk waits: a chunk
            # carries the live rows' token-step for nothing, and after the
            # last one that waits the readback comes at once, so that rows
            # which ended retire and the prompts queued behind them bring
            # the next chunks
            outs, began = [self._dispatch_decode(active)], {}
        with phase(acc, "step_wait", flight):
            # every token-step's tokens [B] (the decode program's [K, B])
            # and the model's counts, in one readback
            outs = self._jax.device_get(outs)
            flight.drain()
            for _, stepped in outs:
                self._add_model_counts(stepped)
            toks = np.concatenate([np.atleast_2d(t) for t, _ in outs])
        with phase(acc, "emit", flight):
            self.steps += len(toks)
            now = time.time()
            for row in began:  # the first token exists
                p = self._slot_pending[row]
                p.t_first = now
                counts["admitted"] += 1
                self._recent.append((p.t_admit - p.t_submit,
                                     p.t_first - p.t_admit))
            self._emit(toks, [r for r in active
                              if r not in self._prefilling], began)
        return len(active)

    def _dispatch_mixed(self, rows: int):
        """Dispatch the chunks that wait, oldest row first and at most K of
        them, each with a decode token-step of the live rows, and return
        their tokens and counts still on the device ([(tokens, counts)], a
        mixed step each) and, for each row whose prompt ended, the
        token-step that made its first token. Mixed steps chain on the
        device: each takes the last one's tokens and the rest of its key,
        and a row whose prompt ends is live from the next one on with its
        first token left there, so all a program is handed (chunk tokens,
        offsets, tables) is known without reading anything back, and none
        waits for the host."""
        jnp, np = self._jnp, self._np
        acc, counts, flight = self._phase, self._counts, self._flight
        K, C = self.steps_per_iter, self._chunk
        pool, sink = self.kv_pool, self.kv_pool.sink_page
        with phase(acc, "assemble", flight, rows=rows):
            if self._pool is None:  # the engine's first admission
                self._pool = pool.allocate()
            key = self._iteration_key()
            last = jnp.asarray(self._slot_last)
            # a row with chunks to go is idle in the decode half: offset 0
            off = self._slot_offset.copy()
            # what the iteration's token-steps fetch, and how much of it is
            # live: once an iteration, as the decode program's
            counts["iterations"] += 1
            self._count_positions(off[off > 0], min(K, sum(
                len(self._slot_prompt[r][0]) // C - int(self._slot_chunks[r])
                for r in self._prefilling)))
        # the most whole chunks a prompt can have
        per = C // pool.page_tokens
        most = -(-(self.cfg.max_seq - self.max_new_tokens) // C)
        began, outs = {}, []
        while self._prefilling and len(outs) < K:
            row = self._prefilling[0]
            arr, true_len = self._slot_prompt[row]
            index = int(self._slot_chunks[row])
            ends = (index + 1) * C >= len(arr)
            with phase(acc, "prefill", flight, chunk_index=index,
                       cap=pool.row_tokens(row)):
                live = off > 0
                # the chunk's table: the row's pages, sink entries past
                # them, as many whole chunks as the prompt has rounded up
                # to K of them. The program has a branch a chunk of its
                # table (and jit a program a table length), so prompts of
                # up to K chunks share one, of up to 2 K the next
                reach = min(-(-(len(arr) // C) // K) * K, most) * per
                chunk_pages = np.full(reach, sink, np.int32)
                mine = pool.table[row, :reach]
                chunk_pages[:len(mine)] = mine
                # an idle row's junk write at positions 0, 1, ... must find
                # the sink: the prefilling row's pages go in as the chunk's
                # alone
                table = np.where(live[:, None], pool.table, sink)
                # the head's row of the chunk, and the last of its real
                # positions: the prompt's last token, or the chunk's (whose
                # logits nobody reads)
                at = np.int32(true_len - 1 - index * C if ends else C - 1)
                # where the model keeps a state a slot, its entry is the row's
                slot = (np.int32(row),) if pool.state_spec else ()
                self._pool, last, key, stepped = self._mixed_step(
                    self.params, self._pool, arr[index * C:(index + 1) * C],
                    chunk_pages, np.int32(index), at,
                    np.int32(row if ends else -1), last, off.copy(), table,
                    key, *slot)
                flight.fill()
                counts["mixed_steps"] += 1
                counts["prefill_positions"] += C
                if C in self._prefill_kernel:
                    counts["prefill_kernel_positions"] += C
                counts["chunk_positions_live"] += min(
                    C, true_len - index * C)
                off[live] += 1
                self._slot_chunks[row] += 1
                if ends:  # live from the next token-step on
                    self._prefilling.pop(0)
                    off[row] = self._slot_offset[row] = true_len
                    began[row] = len(outs)
                outs.append((last, stepped))
        return outs, began

    def _iteration_key(self):
        """The key of an iteration's programs, which split it further on
        the device. Greedy decoding draws nothing: no program is dispatched
        for a key it would not read."""
        if self.temperature > 0:
            self._key, sub = self._jax.random.split(self._key)
            return sub
        return self._key

    def _count_positions(self, offsets, steps: int) -> None:
        """The KV positions a decode program of ``steps`` token-steps over
        live rows at ``offsets`` fetches (each row's pages up to its last
        step's length), and how many of them are live."""
        page = self.kv_pool.page_tokens
        self._counts["slab_positions"] += int(
            (-(-(offsets + steps) // page) * page).sum())
        self._counts["live_positions"] += int(offsets.sum())

    def _add_model_counts(self, stepped) -> None:
        np = self._np
        for name, c in stepped.items():
            self._model_counts[name] = self._model_counts.get(
                name, 0) + c.astype(np.int64)

    def _emit(self, toks, rows, began) -> None:
        """Hand each of ``rows`` what its budget allows of the iteration's
        tokens ``toks`` [T, B]. ``began`` maps a row whose prompt ended in
        this iteration to the token-step that made its first token: its
        tokens start there, and its cache holds the prompt and every token
        but the last."""
        T = len(toks)
        for r in rows:
            start = began.get(r, 0)
            # a row finishing mid-iteration consumes only what
            # its budget allows; the surplus decoded junk wrote
            # beyond its end, into its OWN pages or into the
            # sink, where the lengths keep it invisible
            take = min(T - start, int(self._slot_budget[r]))
            self._slot_out[r].extend(
                int(t) for t in toks[start:start + take, r])
            self._slot_last[r] = int(toks[start + take - 1, r])
            self._slot_offset[r] += take - (r in began)
            self._slot_budget[r] -= take
            if self._slot_budget[r] <= 0:
                self._retire(r)

    def _publish(self) -> None:
        """Engine thread (and the constructor, before it starts): put a
        copy of the accumulators where ``engine_stats`` finds it. Taken
        between iterations, so the copy's numbers belong to one instant."""
        self._published = {
            "phase_s": {k: v[0] for k, v in self._phase.items()},
            "phase_cpu_s": {k: v[1] for k, v in self._phase.items()},
            "starved_s": {k: v[2] for k, v in self._phase.items()},
            "stalls": list(self._stalls),
            **self._counts, "recent": list(self._recent),
            # bytes a cached position holds, bytes a slot's state holds
            # (0: the model keeps none), and the model's own counts as plain
            # lists
            "cache_token_bytes": self.kv_pool.token_bytes,
            "state_row_bytes": self.kv_pool.state_row_bytes,
            **{k: v.tolist() for k, v in self._model_counts.items()}}

    def engine_stats(self) -> Dict[str, Any]:
        """Where the engine thread's time went and what the decode step
        attended over, cumulative since the engine started (a reader
        subtracts two snapshots): wall and thread-CPU seconds by phase,
        iterations, KV positions the iteration's token-steps fetch (each
        live row's pages up to its last step's length) and the live ones
        among them (both summed **once an iteration**, at assembly, from
        the rows live then: the decode program's K token-steps, or as many
        mixed steps as chunks wait, at most K; so ``live_positions`` over
        ``iterations`` is a mean a token-step's assembly in both kinds of
        iteration), requests admitted,
        the positions their prefill programs computed (the sum of the
        buckets' lengths, or of the chunks') and those of buckets whose
        program attends in the flash kernel (the model's
        ``prefill_takes_kernel``), ``mixed_steps`` (token-steps that carried
        a chunk of a prompt) and ``chunk_positions_live`` (prompt positions
        in them that were not padding; both 0 where prompts are prefilled
        whole),
        ``(queue_wait_s, prefill_s)`` of the newest 512 of them, oldest
        first, the bytes a cached position holds, and whatever the model's
        decode step counts of itself, added up by name (the expert model:
        ``expert_tokens`` [E], ``experts_touched``, ``expert_layer_steps``;
        a model with a state: ``state_rows_stepped``, ``state_rows_fetched``,
        ``ssm_layer_steps``, of the decode program's token-steps alone, and
        where its prompts ride in chunks ``mixed_state_rows_stepped`` of the
        mixed steps; one that holds a share of its experts also
        ``expert_assignments`` and ``expert_assignments_held``; the
        layer-pattern model's mixed steps, under names of their own:
        ``mixed_state_rows_stepped``, the live decode rows whose state the
        update kernel moved, summed over the state layers,
        ``mixed_ssm_layer_steps``, that kernel's calls,
        ``mixed_expert_layer_steps``, the expert layers that ran,
        ``mixed_expert_assignments_held``, the assignments of the chunks'
        real positions and the live decode rows together to experts held
        here, and ``mixed_experts_touched``, the held experts with at least
        one of them, summed over the expert layers).

        ``starved_s`` (the keys of ``phase_s``) is, of each phase's wall
        seconds, the part in which nothing the engine dispatched was still
        unread: the chip had no work from this thread. The queue fills when
        a program's call returns (the decode program, the mixed step, a
        prefill program; not ``random.split``'s two tiny programs, nor an
        upload) and drains when the readback that takes the last outstanding
        result returns (``device_get`` of an iteration's tokens, the first
        token's ``int`` in ``_admit``). A block between a drain and the next
        fill counts whole, the block of the fill up to the call's return,
        the block of the drain from the readback's return; ``idle_wait``
        counts nothing (there is no work). So ``starved_s["prefill"]`` over
        ``admitted`` is the host's part of an admission where prompts are
        prefilled whole, and ``phase_s["prefill"] - starved_s["prefill"]``
        the wait for the program. It is a **lower bound** of the device's
        idle time (the way from a call's return to the program's start on
        the chip, and from its end to the readback's return, is left out),
        on the host's clock, over every second and with no profiler; the
        trace's idle share is the device's own reading over its traced
        seconds: the two are compared, not merged. When a readback overlaps
        the next dispatch, phases stop being starved without getting
        shorter: this is the number that shows it.

        ``stalls`` is the newest 16 iterations, oldest first, whose wall
        seconds (the work phases' since the gate) passed 4 x the median of
        the 32 iterations before them: ``{"t_end": time.time(), "wall_s",
        "median_s", "phase_s", "phase_cpu_s", "starved_s", "rows",
        "token_steps"}``, the three dicts that iteration's own. A window
        that lost seconds names the phase, says whether the thread was on a
        core (wall against CPU) and whether the chip had work; warm-up's
        compiles are rows too, so a reader keeps those whose ``t_end`` lies
        inside its window (this process's ``time.time()``).
        Any thread may call it; the copy is the caller's."""
        snap = self._published
        return {**snap, "phase_s": dict(snap["phase_s"]),
                "phase_cpu_s": dict(snap["phase_cpu_s"]),
                "starved_s": dict(snap["starved_s"]),
                "stalls": copy.deepcopy(snap["stalls"]),
                "recent": list(snap["recent"])}

    def kv_stats(self) -> Dict[str, Any]:
        """Pool occupancy snapshot for metrics/benchmarks."""
        return {**self.kv_pool.stats(),
                "kv_backpressure": self.kv_backpressure}


def pack_weights(params, precision: str = "bf16") -> Dict[str, Any]:
    """Quantize a param tree for the movement plane: per-leaf
    :func:`~..core.codec.quantize_array` payloads (bf16 ~2x, int8 ~4x
    smaller than f32), so shipping weights to a cold replica moves a
    fraction of the bytes a full-precision pickle would. Counted under
    ``rmt_collective_quantized_ops_total{op="serve.weights"}``."""
    import jax
    import numpy as np

    from ..core import codec

    leaves, treedef = jax.tree_util.tree_flatten(params)
    payloads = [codec.quantize_array(np.asarray(leaf, dtype=np.float32),
                                     precision) for leaf in leaves]
    codec.count_quantized_op("serve.weights", precision)
    return {"treedef": treedef, "leaves": payloads, "p": precision}


def unpack_weights(payload: Dict[str, Any]):
    """Inverse of :func:`pack_weights` — dequantize each leaf to f32 and
    rebuild the param tree on the replica's device."""
    import jax
    import jax.numpy as jnp

    from ..core import codec

    leaves = [jnp.asarray(codec.dequantize_array(p))
              for p in payload["leaves"]]
    return jax.tree_util.tree_unflatten(payload["treedef"], leaves)


class LLMServer:
    """Deployment class: KV-cached batched generation on one chip.

    The model is ``config`` (a configuration object of models/: a
    ``TransformerConfig``, a ``LatentMoEConfig``, a ``HybridSSMConfig``, a
    ``NemotronHConfig``) or,
    without one, the
    ``TransformerConfig`` that ``preset`` names; the engine asks
    ``models.serving_model`` for its functions, and for its parameters from
    ``seed`` unless ``init`` (``init(key, cfg)`` -> the model's parameter
    tree) makes them on the replica.
    ``user_config`` (reconfigure) can retune ``max_new_tokens`` /
    ``temperature`` without a redeploy. ``weights`` (a
    :func:`pack_weights` payload) skips the replica-side param init —
    the cold-start path for scale-up replicas; both paths time their
    init under ``rmt_serve_cold_start_seconds{source=shipped|init}``."""

    def __init__(self, preset: str = "gpt2-small",
                 config: Any = None,
                 init: Any = None,
                 max_batch_size: int = 8,
                 max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 pad_multiple: int = 64,
                 seed: int = 0,
                 steps_per_iter: int = 8,
                 kv_page_tokens: Optional[int] = None,
                 kv_pool_bytes: Optional[int] = None,
                 weights: Optional[Dict[str, Any]] = None):
        t0 = time.monotonic()
        import jax

        from ..models import gpt, serving_model
        from ..utils.compile_cache import CompileCounter

        # every program this replica compiles from here on (stats())
        self._compiles = CompileCounter()
        self.cfg = config if config is not None else gpt.PRESETS[preset]
        model = serving_model(self.cfg)
        if max_new_tokens + pad_multiple > self.cfg.max_seq:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} leaves no room for a "
                f"{pad_multiple}-token prompt bucket within the model's "
                f"max_seq={self.cfg.max_seq}")
        if weights is not None:
            self.params = unpack_weights(weights)
            cold_source = "shipped"
        else:
            self.params = (init or model.init_params)(
                jax.random.PRNGKey(seed), self.cfg)
            cold_source = "init"
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.pad_multiple = pad_multiple
        self.max_batch_size = max_batch_size
        self.seed = seed
        self._stats = {"requests": 0, "batches": 0, "generated_tokens": 0}
        self.steps_per_iter = steps_per_iter
        self.kv_page_tokens = kv_page_tokens
        self.kv_pool_bytes = kv_pool_bytes
        self._engine = self._new_engine()
        try:
            from ..core import metrics_defs as mdefs
            mdefs.serve_cold_start_seconds().observe(
                time.monotonic() - t0, tags={"source": cold_source})
        except Exception:  # noqa: BLE001 — metrics never fail init
            pass

    def _new_engine(self) -> ContinuousBatcher:
        return ContinuousBatcher(
            self.params, self.cfg, max_slots=self.max_batch_size,
            max_new_tokens=self.max_new_tokens,
            temperature=self.temperature, pad_multiple=self.pad_multiple,
            seed=self.seed + 1, steps_per_iter=self.steps_per_iter,
            kv_page_tokens=self.kv_page_tokens,
            kv_pool_bytes=self.kv_pool_bytes)

    # -- config ---------------------------------------------------------------
    def reconfigure(self, user_config: Optional[dict]) -> None:
        if not user_config:
            return
        new_tokens = int(user_config.get(
            "max_new_tokens", self.max_new_tokens))
        if new_tokens + self.pad_multiple > self.cfg.max_seq:
            raise ValueError(
                f"max_new_tokens={new_tokens} leaves no room for a "
                f"{self.pad_multiple}-token prompt bucket within "
                f"max_seq={self.cfg.max_seq}")
        new_temp = float(user_config.get("temperature", self.temperature))
        changed = (new_tokens != self.max_new_tokens
                   or new_temp != self.temperature)
        self.max_new_tokens = new_tokens
        self.temperature = new_temp
        if changed:
            # temperature is baked into the engine's compiled sampler at
            # trace time (and the token budget into its slot accounting):
            # swap in a fresh engine rather than mutating a live one
            old, self._engine = self._engine, self._new_engine()
            old.close()

    # -- request surface ------------------------------------------------------
    def __call__(self, request: Any = None) -> Dict[str, Any]:
        """HTTP entrypoint: {"tokens": [...]} or {"text": "..."}. Returns
        {"tokens": [...]}. An optional per-request "max_new_tokens"
        (capped by the deployment default) is honored: step-granular
        scheduling makes short requests retire early."""
        if isinstance(request, str):
            request = {"text": request}
        request = request or {}
        tokens = request.get("tokens")
        if tokens is None:
            tokens = _bytes_tokenize(request.get("text", ""),
                                     self.cfg.vocab_size)
        if not tokens:
            tokens = [1]
        out = self.generate(tokens,
                            max_new_tokens=request.get("max_new_tokens"))
        return {"tokens": out, "prompt_len": len(tokens)}

    def generate(self, tokens: Sequence[int],
                 max_new_tokens: Optional[int] = None) -> List[int]:
        """Generate continuation ids for one prompt (batched under the
        hood with whatever arrives concurrently). ``max_new_tokens`` can
        be set per request (capped by the deployment default)."""
        out = self._engine.submit(list(tokens),
                                  max_new_tokens=max_new_tokens)
        self._stats["requests"] += 1
        self._stats["generated_tokens"] += len(out)
        self._stats["batches"] = self._engine.steps
        return out

    def stats(self) -> dict:
        """Request counters, the KV pool's occupancy, the engine's own
        phases and counts, the device this replica computes on as jax
        reports it, and how many programs it has compiled (a request shape
        that keeps compiling shows here)."""
        import jax

        out = dict(self._stats, kv=self._engine.kv_stats(),
                   engine=self._engine.engine_stats())
        dev = jax.devices()[0]
        out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())}
        out["compile"] = self._compiles.snapshot()
        return out


def llm_deployment(preset: str = "gpt2-small",
                   ray_actor_options: Optional[dict] = None,
                   max_concurrent_queries: int = 64,
                   ship_weights: Optional[str] = None, **kwargs):
    """A ready-to-run Application serving ``preset``:

        import ray_memory_management_tpu.serve as serve
        handle = serve.run(serve.llm_deployment("gpt2-small"))
        serve.get_handle("LLM").remote({"tokens": [1, 2, 3]})

    On a TPU host pass ``ray_actor_options={"num_tpus": 1}`` so the
    replica takes a chip lease (TPU_VISIBLE_CHIPS isolation) and the
    decode program runs on the chip.

    ``ship_weights="bf16"|"int8"`` initializes params ONCE on the driver
    and ships them quantized to every replica (:func:`pack_weights` over
    the movement-plane codec) instead of each replica re-initializing —
    the scale-up cold-start path. The payload is also put into the object
    store so the controller can place new replicas near the tier holding
    it (the ``placement_hint`` in the deployment config)."""
    placement_hint = None
    if ship_weights:
        import jax

        from ..models import gpt

        cfg = gpt.PRESETS[preset]
        seed = kwargs.get("seed", 0)
        params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
        kwargs["weights"] = pack_weights(params, precision=ship_weights)
        try:
            from .. import api as core_api

            placement_hint = core_api.put(kwargs["weights"]).hex()
        except Exception:  # noqa: BLE001 — the hint is best-effort; a
            placement_hint = None  # driver without a running runtime
            # still gets weights shipped via the deployment config
    return deployment(
        LLMServer, name="LLM", ray_actor_options=ray_actor_options,
        max_concurrent_queries=max_concurrent_queries,
        placement_hint=placement_hint,
    ).bind(preset=preset, **kwargs)


__all__ = ["ContinuousBatcher", "LLMServer", "llm_deployment",
           "pack_weights", "unpack_weights"]
