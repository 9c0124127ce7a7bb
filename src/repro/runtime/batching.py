"""Continuous-batching scheduler over the paged serving cache.

The serving runtime (DESIGN.md §12) decouples *requests* from *slots*:
requests arrive on a queue (Poisson-style in the benchmark trace), the
scheduler admits them into free decode slots as pool pages allow, and
every decode step runs the whole churning batch through ONE jitted
:func:`repro.runtime.steps.make_paged_serve_step` — batch composition
changes flow through block-table / length *values*, never through new
traces, so ``engine.stats()`` launch counts stay flat while sequences
come and go.

Scheduling policy (deliberately simple, and deterministic so evict →
re-admit is greedy-token-identical to an uninterrupted run):

  * FIFO admission with head-of-line blocking: the queue head is
    admitted iff a slot is free and the free list covers its context
    (+1 headroom page-worth for the first decode write); nothing behind
    it jumps ahead.
  * Per-step growth: before each decode step every active slot is grown
    to cover position ``length`` (the one being written).  When the pool
    runs dry mid-decode, the *most recently admitted* sequence is
    evicted — its pages are freed and it re-enters the queue front with
    its prompt + tokens generated so far; re-admission re-prefills that
    full context, which under greedy decoding reproduces the exact
    token stream.
  * Admission overflow never crashes: requests simply wait.

Everything host-side here is numpy/python — the device only ever sees
the shape-stable step inputs.

Timing (DESIGN.md §15): each phase of ``step()`` runs under
``_phase(key)``, which adds its ``time.perf_counter()`` seconds to
``phase_seconds[key]`` and opens the profiler span ``serve.<key>`` — on
the same clock as the device operations when a trace is recorded.  The
top-level phases (admission, prefill, grow, eviction, tables, decode)
never overlap: one nested in another is left out of the outer one, so
their sum is the time inside ``step()`` less some bookkeeping.  Dotted
keys (``decode.dispatch``, ``decode.emit``) are parts of the phase
before the dot and count inside it; the rest of ``decode`` is the span
``serve.decode.sync``, the wait for the device.  ``queue_wait`` is a
counter: seconds from entering the queue to admission, summed over
admissions.  ``serve.step`` spans a whole ``step()``.

Four more keys are counts, not seconds (:data:`EXPERT_COUNTERS`):
``expert_rows.<decode|prefill>``, the token rows routed to experts this
chip holds, and ``experts_hit.<decode|prefill>``, the held experts with
at least one row, each summed over MoE layers and steps.  The jitted
steps return them beside the tokens and the engine reads them with the
readback the step already does; models without experts return zeros.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.models.attention import PageSpec
from repro.runtime import steps as steps_lib
from repro.runtime.pages import (OutOfPages, PagePool, init_serving_cache,
                                 pages_for, refresh_tables, write_prefill)

# phase_seconds keys that count rows and experts (module docstring).
EXPERT_COUNTERS = ("expert_rows.decode", "experts_hit.decode",
                   "expert_rows.prefill", "experts_hit.prefill")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray        # (L,) int32
    max_new: int
    arrival: float = 0.0      # scheduler-tick time the request appears


@dataclasses.dataclass
class _Seq:
    """Host-side state of one admitted (or evicted-and-queued) request."""
    req: Request
    generated: List[int] = dataclasses.field(default_factory=list)
    evictions: int = 0
    admit_order: int = -1     # monotonic stamp of the latest admission
    t_queued: float = 0.0     # when it last entered the queue (submit or
                              # eviction), on time.perf_counter()
    t_last: float = 0.0       # when the previous token was emitted

    @property
    def context(self) -> np.ndarray:
        """prompt + generated-so-far — what a re-prefill must replay."""
        gen = np.asarray(self.generated, np.int32)
        return np.concatenate([self.req.prompt.astype(np.int32), gen])

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.req.max_new


class ContinuousBatchingEngine:
    """Admission/eviction scheduler + single-launch paged decode loop."""

    def __init__(self, cfg, params, *, num_slots: int, spec: PageSpec):
        if cfg.encoder_decoder:
            raise ValueError("continuous batching serves decoder-only archs")
        self.cfg = cfg
        self.params = params
        self.spec = spec
        self.num_slots = num_slots
        self.max_len = spec.max_blocks * spec.page_size

        self.pool = PagePool(spec, num_slots)
        self.cache = init_serving_cache(cfg, num_slots, spec)
        self._step = jax.jit(steps_lib.make_paged_serve_step(cfg),
                             donate_argnums=(1,))
        self._prefills: Dict[int, object] = {}  # context length -> jitted

        self.queue: deque = deque()
        self.slots: List[Optional[_Seq]] = [None] * num_slots
        self.lengths = np.zeros(num_slots, np.int64)
        self.next_token = np.zeros(num_slots, np.int32)
        self.tick = 0
        self.evictions = 0
        self._admit_counter = 0
        self.finished: Dict[int, _Seq] = {}
        self.token_latencies: List[float] = []
        self._tables_dirty = True
        # Seconds per phase of step() (module docstring), kept by _phase;
        # every key exists from the start.
        self.phase_seconds: Dict[str, float] = dict.fromkeys((
            "admission", "prefill", "grow", "eviction", "tables", "decode",
            "decode.dispatch", "decode.emit", "queue_wait")
            + EXPERT_COUNTERS, 0.0)
        self._nested_s = 0.0  # top-level phases inside the open one
        # Expert counts of prefills not read back yet (re-admissions).
        self._unread_counts: List[jax.Array] = []

    # -- warm-start ---------------------------------------------------------

    def warmup(self, prompt_lens=(), *, manifest: Optional[str] = None
               ) -> Dict:
        """Trace/build everything a serving loop will touch, pre-traffic.

        Three layers, outermost first (DESIGN.md §15):

          * kernel families — ``engine.warmup`` over a descriptor
            manifest (or ``configure(warm_start=...)``), resolving plans
            through the tuned tier and building each kernel once;
          * prefill traces — one jit trace per distinct prompt length in
            ``prompt_lens`` (the per-length ``_prefill_fn`` cache);
          * the decode step — traced once on an all-inactive batch (no
            active slot, so nothing scatters into the paged cache; the
            donated cache buffer is reassigned like a real step).

        After this returns, a serving run with the same shapes performs
        zero kernel builds, zero plan-cache misses and zero new traces —
        provable via ``engine.stats()``.  Returns a summary dict.
        """
        from repro.core.config import get_config
        t0 = time.perf_counter()
        kernels: Dict[str, int] = {}
        if manifest is not None or get_config().warm_start:
            kernels = engine.warmup(manifest=manifest)
        lengths = sorted({int(L) for L in prompt_lens})
        for L in lengths:
            jax.block_until_ready(self._prefill_fn(L)(
                self.params, {"tokens": jnp.zeros((1, L), jnp.int32)}))
        toks, self.cache, _ = self._step(
            self.params, self.cache,
            jnp.zeros((self.num_slots, 1), jnp.int32),
            jnp.zeros((self.num_slots,), jnp.int32),
            jnp.zeros((self.num_slots,), bool))
        jax.block_until_ready(toks)
        return {"seconds": time.perf_counter() - t0, "kernels": kernels,
                "prefill_lengths": lengths}

    # -- submission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        now = time.perf_counter()
        self.queue.append(_Seq(req=req, t_queued=now, t_last=now))

    # -- internals ----------------------------------------------------------

    @contextlib.contextmanager
    def _phase(self, key: str):
        """Time the block into ``phase_seconds[key]`` under the profiler
        span ``serve.<key>``.  A top-level (undotted) key leaves out the
        top-level phases nested in it; a dotted key counts them."""
        outer, self._nested_s = self._nested_s, 0.0
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("serve." + key):
                yield
        finally:
            dt = time.perf_counter() - t0
            top = "." not in key
            self.phase_seconds[key] += dt - self._nested_s if top else dt
            self._nested_s = outer + (dt if top else self._nested_s)

    def _prefill_fn(self, length: int):
        fn = self._prefills.get(length)
        if fn is None:
            fn = jax.jit(steps_lib.make_prefill_step(self.cfg, length))
            self._prefills[length] = fn
        return fn

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _admit(self, seq: _Seq, slot: int) -> None:
        # Fresh admission prefills the prompt and emits its argmax — same
        # as the static path.  RE-admission replays prompt + all-but-last
        # generated token: that reproduces exactly the cache an
        # uninterrupted run would hold (the last emitted token is never
        # in the cache yet), then the normal decode step recomputes from
        # it — so evict/re-admit cycles stay greedy-token-identical.
        self.phase_seconds["queue_wait"] += time.perf_counter() - seq.t_queued
        readmit = bool(seq.generated)
        ctx = seq.context[:-1] if readmit else seq.context
        L = len(ctx)
        page_ids = self.pool.owned_pages(slot)
        page_ids += self.pool.grow(slot, L)
        with self._phase("prefill"):
            logits, dense, counts = self._prefill_fn(L)(
                self.params, {"tokens": jnp.asarray(ctx)[None, :]})
            self.cache = write_prefill(self.cache, dense, slot=slot,
                                       length=L, page_ids=page_ids,
                                       page_size=self.spec.page_size)
            self._unread_counts.append(counts)
            # The argmax readback is the admission's one device sync.  A
            # re-admission reads nothing back: its prefill finishes on the
            # device inside the next decode's sync.
            if readmit:
                tok = seq.generated[-1]
            else:
                tok = int(self._read_back(jnp.argmax(logits[0])))
        if not readmit:
            self._emit(seq, tok)
        self.slots[slot] = seq
        self.lengths[slot] = L
        self.next_token[slot] = tok
        self._tables_dirty = True

    def _read_back(self, out, decode_counts=None):
        """``out`` on the host, read in one transfer with the expert
        counts of the unread prefills and of this decode step (if any),
        which are added to their counters."""
        prefills, self._unread_counts = self._unread_counts, []
        out, prefills, decode_counts = jax.device_get(
            (out, prefills, decode_counts))
        read = [("prefill", c) for c in prefills]
        if decode_counts is not None:
            read.append(("decode", decode_counts))
        for key, c in read:
            self.phase_seconds["expert_rows." + key] += int(c[0])
            self.phase_seconds["experts_hit." + key] += int(c[1])
        return out

    def _emit(self, seq: _Seq, tok: int) -> None:
        now = time.perf_counter()
        seq.generated.append(tok)
        self.token_latencies.append(now - seq.t_last)
        seq.t_last = now

    def _release(self, slot: int) -> None:
        self.pool.release(slot)
        self.slots[slot] = None
        self.lengths[slot] = 0
        self._tables_dirty = True

    def _evict_for_growth(self, needy_slot: int) -> None:
        """Free pages by evicting the most recently admitted other slot."""
        victims = [i for i, s in enumerate(self.slots)
                   if s is not None and i != needy_slot]
        if not victims:
            raise OutOfPages(
                f"slot {needy_slot} cannot grow and no other sequence can "
                f"be evicted — pool too small for one sequence")
        # LIFO victim choice: the most recently admitted sequence has the
        # least decode investment to replay on re-admission.
        with self._phase("eviction"):
            victim = max(victims, key=lambda i: self.slots[i].admit_order)
            seq = self.slots[victim]
            seq.evictions += 1
            self.evictions += 1
            self._release(victim)
            seq.t_queued = time.perf_counter()
            self.queue.appendleft(seq)

    def _try_admissions(self) -> None:
        while self.queue:
            seq = self.queue[0]
            L = len(seq.context)
            if L + 1 > self.max_len:
                raise ValueError(
                    f"request {seq.req.rid} context {L}+1 exceeds "
                    f"max mappable length {self.max_len}")
            slot = self._free_slot()
            # +1 headroom: the first decode step writes position L.
            if slot is None or not self.pool.can_admit(L, headroom=1):
                break  # head-of-line blocking keeps admission FIFO-fair
            self.queue.popleft()
            self._admit_counter += 1
            seq.admit_order = self._admit_counter
            self._admit(seq, slot)

    def _grow_active(self) -> None:
        for slot, seq in enumerate(self.slots):
            if seq is None:
                continue
            while True:
                try:
                    if self.pool.grow(slot, int(self.lengths[slot]) + 1):
                        self._tables_dirty = True
                    break
                except OutOfPages:
                    self._evict_for_growth(slot)

    # -- one scheduler tick -------------------------------------------------

    def _retire_done(self) -> None:
        for slot, seq in enumerate(self.slots):
            if seq is not None and seq.done:
                self.finished[seq.req.rid] = seq
                self._release(slot)

    def step(self) -> int:
        """Retire finished sequences, admit what fits, grow, run ONE
        decode launch over the live batch.  Returns the number of live
        slots this step decoded (0 = idle tick)."""
        with jax.profiler.TraceAnnotation("serve.step"):
            with self._phase("admission"):
                self._retire_done()
                self._try_admissions()
                # Admission emits one token (the prefill argmax) —
                # sequences that completed right there retire without
                # ever decoding.
                self._retire_done()
            self.tick += 1
            if not any(s is not None for s in self.slots):
                return 0
            # Growth may evict — the mask MUST be taken after it, or an
            # evicted slot would decode as active and scatter its KV
            # through the zeroed block table into page 0 (owned by
            # someone else).
            with self._phase("grow"):
                self._grow_active()
            active_mask = np.array([s is not None for s in self.slots])
            n_active = int(active_mask.sum())
            if n_active == 0:
                return 0
            if self._tables_dirty:
                with self._phase("tables"):
                    self.cache = refresh_tables(self.cache,
                                                self.pool.device_tables())
                self._tables_dirty = False
            with self._phase("decode"):
                with self._phase("decode.dispatch"):
                    toks, self.cache, counts = self._step(
                        self.params, self.cache,
                        jnp.asarray(self.next_token)[:, None],
                        jnp.asarray(self.lengths, dtype=jnp.int32),
                        jnp.asarray(active_mask))
                with jax.profiler.TraceAnnotation("serve.decode.sync"):
                    toks = self._read_back(toks, counts)[:, 0]
                with self._phase("decode.emit"):
                    for slot, seq in enumerate(self.slots):
                        if seq is None or not active_mask[slot]:
                            continue
                        self._emit(seq, int(toks[slot]))
                        self.lengths[slot] += 1
                        self.next_token[slot] = int(toks[slot])
            return n_active

    # -- driver -------------------------------------------------------------

    def run(self, requests: List[Request], *,
            max_steps: int = 100_000) -> Dict:
        """Drive the scheduler until every request finished.

        Requests become visible when ``self.tick`` reaches their
        ``arrival`` (tick-time Poisson arrivals in the benchmark trace).
        Returns per-request outputs plus throughput / latency / launch
        metrics."""
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        stats0 = engine.stats()
        t0 = time.perf_counter()
        decode_steps = 0
        while pending or self.queue or any(s is not None
                                           for s in self.slots):
            while pending and pending[0].arrival <= self.tick:
                self.submit(pending.pop(0))
            if self.step():
                decode_steps += 1
            if self.tick > max_steps:
                raise RuntimeError("scheduler did not converge "
                                   f"within {max_steps} steps")
        wall = time.perf_counter() - t0
        stats1 = engine.stats()

        lat = np.asarray(self.token_latencies)
        total_tokens = sum(len(s.generated) for s in self.finished.values())
        fam = "flash_decode"
        launches = (stats1.get(fam, {}).get("launches", 0)
                    - stats0.get(fam, {}).get("launches", 0))
        return {
            "outputs": {rid: np.asarray(s.generated, np.int32)
                        for rid, s in self.finished.items()},
            "evictions": {rid: s.evictions
                          for rid, s in self.finished.items()},
            "metrics": {
                "requests": len(self.finished),
                "total_tokens": int(total_tokens),
                "decode_steps": decode_steps,
                "wall_seconds": wall,
                "tokens_per_s": total_tokens / max(wall, 1e-9),
                "p50_token_latency_s": float(np.percentile(lat, 50))
                if lat.size else 0.0,
                "p99_token_latency_s": float(np.percentile(lat, 99))
                if lat.size else 0.0,
                "evictions": self.evictions,
                "flash_decode_launches": int(launches),
                "phase_seconds": dict(self.phase_seconds),
            },
            "engine_stats": stats1,
        }


def poisson_trace(*, num_requests: int, rate: float, prompt_lens,
                  max_new, vocab_size: int, seed: int = 0) -> List[Request]:
    """A reproducible Poisson-style request trace.

    ``rate``: expected arrivals per scheduler tick; inter-arrival gaps
    are exponential.  ``prompt_lens``/``max_new`` may be ints or
    (lo, hi) ranges sampled uniformly.  Everything derives from ``seed``
    so benchmark runs are comparable across commits."""
    rng = np.random.default_rng(seed)

    def draw(spec):
        if isinstance(spec, int):
            return spec
        lo, hi = spec
        return int(rng.integers(lo, hi + 1))

    t = 0.0
    out = []
    for rid in range(num_requests):
        t += float(rng.exponential(1.0 / max(rate, 1e-9)))
        L = draw(prompt_lens)
        out.append(Request(
            rid=rid,
            prompt=rng.integers(0, vocab_size, size=L).astype(np.int32),
            max_new=draw(max_new),
            arrival=t))
    return out
