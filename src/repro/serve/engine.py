"""Batched serving engine: continuous-batching decode over a KV cache.

Production concerns covered at container scale:
  * request queue with admission to fixed batch slots;
  * continuous batching (``scheduler="continuous"``, the default): ONE
    batched KV cache of shape (slots, cache_len, ...) plus a host-side
    per-slot occupancy vector, ONE jitted ragged decode step per
    scheduler tick over all occupied slots (through
    ``ops.decode_attention``, the Pallas ragged decode kernel's entry
    point), and prefill-on-admit that writes a freed slot's cache rows
    while the other slots keep decoding — requests join and leave the
    running batch at token boundaries, finished slots are masked via
    ``kv_len`` rather than drained;
  * the pre-batching scheduler (``scheduler="slot"``) is kept as the
    measured baseline: one jitted decode call per slot per token, the
    per-token host round-trips the AI-tax paper predicts dominate once
    the AI core is fast (``benchmarks/fig_decode_batching.py`` measures
    the gap);
  * per-request AI-tax events via the same EventLog as the paper's
    pipeline: queue wait, and in each continuous tick (a profiler step
    ``engine.tick``) one span per host phase — ``pre_admit``, per admit
    ``prefill``, its token read and ``pre_insert``, then the upload,
    ``decode``, the readback and ``post_tokens`` — each stored once over
    the busy slots (amortized per slot on read) and annotated for the
    profiler as ``engine.<phase>``. Every device->host fetch is both
    counted (``d2h_syncs``/``d2h_bytes``) and logged as a transfer so
    the ledger accounts every boundary byte;
  * straggler mitigation hook: slots exceeding ``max_tokens`` are
    evicted, where ``max_tokens`` bounds the total generated tokens
    (prefill's token included — ``max_tokens=1`` emits exactly one
    token and never runs a decode step).

The engine is model-agnostic: any ``repro.models.model.Model`` works
(encoder-decoder caches keep the lock-step scalar layout, so those
models fall back to the slot scheduler). On the container it runs tiny
configs on CPU; the step functions are the same ones the dry-run
lowers for the production mesh.
"""
from __future__ import annotations

import functools
import gc
import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.batching import Batcher
from repro.core.events import EventLog, Timer
from repro.kernels import ops


# Jitted step functions live at module level with the (frozen, hashable)
# Model as a static argument: every engine over the same model shares one
# compiled executable instead of paying a per-instance retrace — the
# decode-batching benchmark times steady-state dispatch, not compilation.
# The kernel implementation is static too: the models read ops' default
# while tracing, so the engine passes the default in force at call time
# and a program traced under one implementation is never reused under
# another.
@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _prefill(model, impl, params, tokens, cache_len):
    with ops.default_impl(impl):
        return model.prefill(params, {"tokens": tokens}, cache_len=cache_len)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _step_fused(model, impl, params, cache, tokens):
    with ops.default_impl(impl):
        logits, cache = model.decode_step(params, cache, tokens)
    return jnp.argmax(logits.reshape(-1)).astype(jnp.int32), cache


@functools.partial(jax.jit, static_argnums=(0, 1))
def _step_plain(model, impl, params, cache, tokens):
    with ops.default_impl(impl):
        return model.decode_step(params, cache, tokens)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _step_batched_fused(model, impl, params, blocks, packed):
    # packed (2, B) int32: row 0 the feedback tokens, row 1 per-slot
    # kv_len — one h2d upload per tick instead of two
    with ops.default_impl(impl):
        logits, blocks = model.decode_step_ragged(
            params, blocks, packed[0][:, None], packed[1])
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), blocks


@functools.partial(jax.jit, static_argnums=(0, 1))
def _step_batched_plain(model, impl, params, blocks, packed):
    with ops.default_impl(impl):
        return model.decode_step_ragged(params, blocks, packed[0][:, None],
                                        packed[1])


@functools.partial(jax.jit, static_argnums=0)
def _insert_slot(model, blocks, one_blocks, slot):
    return model.insert_prefill(blocks, one_blocks, slot)


_GC_OPEN: list = []


def _gc_span(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a profiler span ``python.gc`` around each
    collection, so a device gap a collection causes is charged to it and
    not to the engine phase that was open."""
    if phase == "start":
        span = jax.profiler.TraceAnnotation("python.gc")
        span.__enter__()
        _GC_OPEN.append(span)
    elif _GC_OPEN:
        _GC_OPEN.pop().__exit__(None, None, None)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_tokens: int = 16          # bound on generated tokens (prefill incl.)
    t_submit: float = 0.0
    t_first: float = 0.0          # first token ready (TTFT = t_first - t_submit)
    tokens: list = field(default_factory=list)
    done: bool = False


class ServingEngine:
    def __init__(self, model, params, *, batch_slots: int = 4,
                 cache_len: int = 128, greedy: bool = True,
                 fast_path: bool = True, max_queue: int | None = None,
                 degrade=None, scheduler: str = "continuous"):
        self.model = model
        self.params = params
        self.slots = batch_slots
        self.cache_len = cache_len
        self.log = EventLog(owner="engine")
        if _gc_span not in gc.callbacks:
            gc.callbacks.append(_gc_span)
        if scheduler not in ("continuous", "slot"):
            raise ValueError(f"scheduler must be continuous/slot: {scheduler!r}")
        if model.cfg.encdec and scheduler == "continuous":
            # encoder-decoder caches are lock-step scalar-cur_len trees;
            # the ragged batched layout is decoder-only
            scheduler = "slot"
        self.scheduler = scheduler
        # graceful degradation (duck-typed DegradePolicy, same ladder
        # as the serving cluster): under queue pressure, admitted
        # requests get max_tokens clamped by the current level's
        # service_factor — shorter generations shed work before
        # admission control sheds requests — with the accuracy cost
        # logged as a zero-span "degrade" event per clamped request
        self.degrade = degrade
        self._deg_depth = 0
        self.degrade_timeline: list[tuple[float, int, str]] = []
        # admission bound: submissions beyond max_queue pending requests
        # are rejected at the door (logged as zero-span "reject" events,
        # so tax_report() sees the shed load); None = accept
        # everything and let queue wait absorb the pressure
        self.max_queue = max_queue
        self.rejected = 0
        self._admit_lock = threading.Lock()   # atomic check-then-put
        # admission shares the streaming pipeline's Batcher: submissions
        # land on a topic-like queue and are drained non-blocking into
        # whatever slots are free each scheduler step
        self._pending: queue.Queue = queue.Queue()
        self.admission = Batcher(self._pending, batch_size=batch_slots,
                                 timeout_s=0.0)
        self.active: list[Request | None] = [None] * batch_slots
        self.greedy = greedy
        # ground truth of physical device->host fetches: every blocking
        # read increments these, and the transfer ledger must account
        # the same bytes (tests assert ledger == counters — the
        # unlogged per-token cur_len sync of the pre-batching engine
        # can't silently come back)
        self.d2h_syncs = 0
        self.d2h_bytes = 0
        # continuous-batching state: per-slot occupancy and the token
        # each slot feeds back next tick, BOTH host-resident — reading
        # them never touches the device
        self._kv_len = np.zeros(batch_slots, np.int32)
        self._last_tok = np.zeros(batch_slots, np.int32)
        self._blocks = None          # batched (slots, cache_len, ...) cache
        self._tick_no = 0            # continuous ticks run, for step_num
        # fast_path: greedy token selection is fused into the jitted
        # decode program, so one int32 per slot crosses device->host per
        # step; the unfused path fetches the full logit rows and
        # argmaxes on the host (the classic glue-code pattern the paper
        # taxes)
        self.fast_path = fast_path
        self._decode = functools.partial(
            _step_fused if fast_path else _step_plain, model)
        if scheduler == "continuous":
            self._decode_batch = functools.partial(
                _step_batched_fused if fast_path else _step_batched_plain,
                model)
            self._insert = functools.partial(_insert_slot, model)

    def submit(self, req: Request) -> bool:
        """Queue a request; False when admission control sheds it."""
        req.t_submit = time.perf_counter()
        with self._admit_lock:
            if (self.max_queue is not None
                    and self._pending.qsize() >= self.max_queue):
                self.rejected += 1
                reject = True
            else:
                self._pending.put(req)
                reject = False
        if reject:
            self.log.log(req.rid, "reject", req.t_submit, req.t_submit,
                         int(req.prompt.nbytes))
        return not reject

    @property
    def queue_depth(self) -> int:
        return self._pending.qsize()

    # -- degradation ladder -------------------------------------------------
    def _degrade_tick(self) -> None:
        """Re-evaluate the ladder on the per-slot backlog analogue (no
        breakers here, so the open-fraction input is 0)."""
        if self.degrade is None:
            return
        depth = self.degrade.decide(
            self.queue_depth / max(self.slots, 1), 0.0, self._deg_depth)
        if depth != self._deg_depth:
            self._deg_depth = depth
            self.degrade_timeline.append(
                (time.perf_counter(), depth,
                 self.degrade.level(depth).name))

    def _degrade_clamp(self, req: Request) -> None:
        if self.degrade is None or self._deg_depth <= 0:
            return
        lvl = self.degrade.level(self._deg_depth)
        cap = max(1, int(req.max_tokens * lvl.service_factor))
        if cap < req.max_tokens:
            req.max_tokens = cap
            t = time.perf_counter()
            self.log.log(req.rid, "degrade", t, t,
                         accuracy_proxy=lvl.accuracy_proxy, level=lvl.name)

    # -- single-sequence prefill per admit ----------------------------------
    def _prefill_one(self, req: Request, **tag):
        with Timer(self.log, req.rid, "prefill", int(req.prompt.nbytes),
                   **tag):
            tokens = jnp.asarray(req.prompt[None, :])
            self.log.log_transfer(req.rid, "h2d", int(tokens.nbytes),
                                  "prefill")
            logits, cache = _prefill(self.model, ops.get_default_impl(),
                                     self.params, tokens, self.cache_len)
            jax.block_until_ready(logits)
        with Timer(self.log, req.rid, "transfer", direction="d2h",
                   boundary="prefill", **tag) as read:
            if self.fast_path:
                # argmax on device; only the winning index crosses
                idx = jnp.argmax(logits[0]).astype(jnp.int32)
                nxt = int(idx)
                read.payload_bytes = int(idx.nbytes)
            else:
                row = np.asarray(logits[0])
                nxt = int(np.argmax(row))
                read.payload_bytes = int(row.nbytes)
        self.d2h_syncs += 1
        self.d2h_bytes += read.payload_bytes
        req.tokens.append(nxt)
        req.t_first = time.perf_counter()
        return cache, nxt

    def _finished_early(self, req: Request, finished: list) -> bool:
        """Post-prefill finish check — the generated-token bound counts
        the prefill-produced token, so ``max_tokens=1`` (e.g. a degrade
        clamp) finishes here and never runs a decode step; a prompt
        already at cache capacity likewise never decodes into a full
        cache."""
        if (len(req.tokens) >= req.max_tokens
                or len(req.prompt) >= self.cache_len - 1):
            req.done = True
            finished.append(req)
            return True
        return False

    # -- schedulers ---------------------------------------------------------
    def run(self, max_steps: int = 512) -> list[Request]:
        """Processes the queue to completion (or step limit)."""
        if self.scheduler == "continuous":
            return self._run_continuous(max_steps)
        return self._run_slot(max_steps)

    def _poll_free_slots(self) -> list[tuple[int, Request]]:
        """Drain the submission topic into free slots: each request's
        queue wait is logged and its ``max_tokens`` clamped."""
        free = [i for i in range(self.slots) if self.active[i] is None]
        if not free:
            return []
        polled = list(zip(free, self.admission.poll(len(free))))
        for _, req in polled:
            self.log.log(req.rid, "wait", req.t_submit, time.perf_counter())
            self._degrade_clamp(req)
        return polled

    def _prefill_polled(self, polled, finished: list, **tag) -> list:
        """Prefill each polled request; returns ``(slot, request, cache)``
        for those that still decode. Every prefill of a tick runs before
        any of its slot inserts: at cell sizes the device's memory is
        nearly full, and this order of its large allocations is the one
        the engine is measured with."""
        admitted = []
        for i, req in polled:
            cache, _ = self._prefill_one(req, **tag)
            if not self._finished_early(req, finished):
                admitted.append((i, req, cache))
        return admitted

    def _run_continuous(self, max_steps: int) -> list[Request]:
        """One jitted ragged decode step per tick over all occupied
        slots; admissions prefill into freed slots between ticks."""
        finished: list[Request] = []
        steps = 0
        while (any(self.active) or not self._pending.empty()) \
                and steps < max_steps:
            n = self._tick_no
            self._tick_no += 1
            with jax.profiler.StepTraceAnnotation("engine.tick", step_num=n):
                self._tick(n, finished)
            steps += 1
        return finished

    def _tick(self, n: int, finished: list) -> None:
        """One continuous tick, one span per host phase: each span is
        logged over the requests it serves, tagged ``tick=n``."""
        rids = [r.rid for r in self.active if r is not None]
        with Timer(self.log, rids, "pre_admit", tick=n):
            self._degrade_tick()
            polled = self._poll_free_slots()
            rids += [req.rid for _, req in polled]
        for i, req, cache in self._prefill_polled(polled, finished, tick=n):
            with Timer(self.log, req.rid, "pre_insert", tick=n):
                if self._blocks is None:
                    self._blocks = self.model.init_cache(
                        self.slots, self.cache_len)["blocks"]
                slot = jnp.asarray(i, jnp.int32)
                self.log.log_transfer(req.rid, "h2d", int(slot.nbytes),
                                      "admit")
                # device-side row insert: resident slots' rows untouched
                self._blocks = self._insert(self._blocks, cache["blocks"],
                                            slot)
            self.active[i] = req
            self._kv_len[i] = len(req.prompt)
            self._last_tok[i] = req.tokens[-1]
        idx = [i for i in range(self.slots) if self.active[i] is not None]
        if not idx:
            return
        rids = [self.active[i].rid for i in idx]
        # boundary bytes, padding (idle lanes) included: the whole slot
        # vector crosses in one batched transfer each way
        with Timer(self.log, rids, "transfer", direction="h2d",
                   boundary="decode", tick=n) as up:
            packed = jnp.asarray(np.stack([self._last_tok, self._kv_len]))
            up.payload_bytes = int(packed.nbytes)
        with Timer(self.log, rids, "decode", tick=n, slots_busy=len(idx),
                   queue_depth=self.queue_depth):
            out, self._blocks = self._decode_batch(
                ops.get_default_impl(), self.params, self._blocks, packed)
            jax.block_until_ready(out)
        with Timer(self.log, rids, "transfer", int(out.nbytes),
                   direction="d2h", boundary="decode", tick=n):
            out_host = np.asarray(out)       # the ONE d2h per tick
        self.d2h_syncs += 1
        self.d2h_bytes += int(out_host.nbytes)
        with Timer(self.log, rids, "post_tokens", tick=n):
            nxt = out_host if self.fast_path else out_host.argmax(-1)
            for i in idx:
                req = self.active[i]
                tok_i = int(nxt[i])
                req.tokens.append(tok_i)
                self._last_tok[i] = tok_i
                self._kv_len[i] += 1
                if (len(req.tokens) >= req.max_tokens
                        or self._kv_len[i] >= self.cache_len - 1):
                    # leave at a token boundary: the slot's rows stay in
                    # the cache, masked out by kv_len=0 until a new
                    # admission overwrites them
                    req.done = True
                    finished.append(req)
                    self.active[i] = None
                    self._kv_len[i] = 0
                    self._last_tok[i] = 0

    def _run_slot(self, max_steps: int) -> list[Request]:
        """Baseline scheduler: one jitted decode call per slot per token
        (per-token host round-trips — what continuous batching removes).
        Cache occupancy is tracked host-side; the device is only read
        for token values, and every such read is on the ledger."""
        finished: list[Request] = []
        caches: list = [None] * self.slots
        occ = [0] * self.slots       # host-side cur_len mirror: no d2h read
        steps = 0
        while (any(self.active) or not self._pending.empty()) \
                and steps < max_steps:
            self._degrade_tick()
            for i, req, cache in self._prefill_polled(
                    self._poll_free_slots(), finished):
                self.active[i] = req
                caches[i] = cache
                occ[i] = len(req.prompt)
            # lock-step decode over occupied slots
            for i, req in enumerate(self.active):
                if req is None:
                    continue
                t0 = time.perf_counter()
                tok = jnp.asarray([[req.tokens[-1]]], jnp.int32)
                self.log.log_transfer(req.rid, "h2d", int(tok.nbytes),
                                      "decode")
                if self.fast_path:
                    nxt_dev, caches[i] = self._decode(
                        ops.get_default_impl(), self.params, caches[i], tok)
                    jax.block_until_ready(nxt_dev)
                    self.log.log(req.rid, "decode", t0, time.perf_counter())
                    self.d2h_syncs += 1
                    self.d2h_bytes += int(nxt_dev.nbytes)
                    self.log.log_transfer(req.rid, "d2h",
                                          int(nxt_dev.nbytes), "decode")
                    nxt = int(nxt_dev)
                else:
                    logits, caches[i] = self._decode(
                        ops.get_default_impl(), self.params, caches[i], tok)
                    jax.block_until_ready(logits)
                    self.log.log(req.rid, "decode", t0, time.perf_counter())
                    row = np.asarray(logits[0])
                    self.d2h_syncs += 1
                    self.d2h_bytes += int(row.nbytes)
                    self.log.log_transfer(req.rid, "d2h", int(row.nbytes),
                                          "decode")
                    nxt = int(np.argmax(row))
                req.tokens.append(nxt)
                occ[i] += 1
                if len(req.tokens) >= req.max_tokens \
                        or occ[i] >= self.cache_len - 1:
                    req.done = True
                    finished.append(req)
                    self.active[i] = None
                    caches[i] = None
                    occ[i] = 0
            steps += 1
        return finished

    def tax_report(self) -> dict:
        return self.log.ai_tax(ai_stages={"prefill", "decode"})

    def ttft_samples(self) -> list[float]:
        """Per-request time-to-first-token (submit -> prefill token),
        for every request that produced one."""
        # finished or still-resident requests both carry t_first
        seen = {}
        for ev in self.log.events:
            if ev.stage == "prefill":
                seen[ev.request_id] = ev.t_end
        subs = {}
        for ev in self.log.events:
            if ev.stage == "wait":
                subs[ev.request_id] = ev.t_start
        return [t - subs[rid] for rid, t in seen.items() if rid in subs]
