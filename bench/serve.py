"""Drives the system under test: ``ContinuousBatchingEngine`` on the
pallas backend, fed by the open-loop schedule on the wall clock.

The generator and the server share one thread: between two ``step()``
calls every request that has fallen due is submitted, and while the
engine holds no work the generator sleeps until the next one is due.
Each request is timed from when it was due, so a long step delays the
requests that fall due during it, and how late they were submitted is
reported.  A token's time is when the ``step()`` that emitted it
returned (``step()`` ends by reading its tokens back, so the device has
finished); that adds up to one step to each first token.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.runtime.batching import Request

from bench.load import Planned


@dataclasses.dataclass
class Tracked:
    plan: Planned
    due_at: float                  # perf_counter time it fell due
    seq: object = None             # the engine's record of the request
    submitted_at: float = 0.0
    emits: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class WindowWork:
    """What the window's steps did, for the per-layer readers."""
    decode_contexts: List[List[int]] = dataclasses.field(
        default_factory=list)      # per decode step: live cache lengths
    prefills: List[int] = dataclasses.field(default_factory=list)
    steps: int = 0


class OpenLoop:
    def __init__(self, engine, plan: List[Planned], t_open: float):
        self.engine = engine
        self.t_open = t_open
        self.todo = [Tracked(p, t_open + p.due) for p in plan]
        self.next = 0
        self.tracked: Dict[int, Tracked] = {}
        self.work = WindowWork()

    # -- one pass of the loop ---------------------------------------------

    def _submit_due(self, now: float) -> None:
        with jax.profiler.TraceAnnotation("bench.submit"):
            while (self.next < len(self.todo)
                   and self.todo[self.next].due_at <= now):
                t = self.todo[self.next]
                self.engine.submit(Request(rid=t.plan.rid,
                                           prompt=t.plan.prompt,
                                           max_new=t.plan.max_new))
                t.seq = self.engine.queue[-1]
                t.submitted_at = time.perf_counter()
                self.tracked[t.plan.rid] = t
                self.next += 1

    def _busy(self) -> bool:
        e = self.engine
        return bool(e.queue) or any(s is not None for s in e.slots)

    def _step(self, record: bool) -> None:
        e = self.engine
        # Only the queue's head can be admitted by one step.
        before = {id(s): (s.admit_order, len(s.generated))
                  for s in itertools.islice(e.queue, e.num_slots)}
        with jax.profiler.TraceAnnotation("bench.step"):
            e.step()
        t = time.perf_counter()
        # A request emits only while it holds a slot, and leaves its slot
        # at the start of the step after its last token.
        live = [s for s in e.slots if s is not None]
        for s in live:
            tr = self.tracked.get(s.req.rid)
            if tr is not None:
                tr.emits.extend([t] * (len(s.generated) - len(tr.emits)))
        if not record:
            return
        w = self.work
        w.steps += 1
        for s in live:
            order, gen = before.get(id(s), (s.admit_order, None))
            if gen is not None and s.admit_order != order:
                # admitted in this step: a fresh prompt, or a re-admission
                # that replays prompt + all but the last served token
                w.prefills.append(len(s.req.prompt) + max(gen - 1, 0))
        if live:
            w.decode_contexts.append(
                [int(e.lengths[i]) for i, s in enumerate(e.slots)
                 if s is not None])

    def serve_until(self, t_stop: float, *, record: bool,
                    stop=None) -> float:
        """Serve until ``t_stop`` (or until ``stop()`` holds); returns the
        time the loop ended, after the last step that began before it."""
        while True:
            now = time.perf_counter()
            if now >= t_stop or (stop is not None and stop()):
                return now
            self._submit_due(now)
            if self._busy():
                self._step(record)
            else:
                nxt = (self.todo[self.next].due_at
                       if self.next < len(self.todo) else t_stop)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt, t_stop) - now))

    # -- readings ------------------------------------------------------------

    def lateness(self) -> List[float]:
        return [t.submitted_at - t.due_at for t in self.tracked.values()
                if t.plan.in_window]

    def finished(self) -> Dict[int, tuple]:
        """(prompt, served tokens) of every request that has served all
        of its tokens."""
        return {rid: (t.plan.prompt, np.asarray(t.seq.generated, np.int32))
                for rid, t in self.tracked.items()
                if t.seq is not None and len(t.seq.generated)
                >= t.plan.max_new}

    def first_token_due(self) -> List[int]:
        """Requests due in the window still waiting for a first token."""
        return [t.plan.rid for t in self.todo
                if t.plan.in_window and not t.emits]


def warm(engine, lengths: List[int], vocab: int) -> None:
    """Serve one request of each prompt length with two new tokens: the
    prefill program, the eager prefill-to-page write and the argmax of
    each length, and the decode step at the cell's block-table width."""
    rng = np.random.default_rng(0)
    for i, L in enumerate(lengths):
        engine.submit(Request(rid=-1 - i, prompt=rng.integers(
            0, vocab, L).astype(np.int32), max_new=2))
    while engine.queue or any(s is not None for s in engine.slots):
        engine.step()


def memory_peak_bytes() -> Optional[int]:
    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None for s in stats):
        return None
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
