"""Spans and counters of ContinuousBatchingEngine.step() (DESIGN.md §15).

Every key of ``phase_seconds`` exists before the first step, the parts
of ``decode`` fit inside it, ``queue_wait`` counts the wait of a request
held behind another and of a re-admission from its eviction, the
``decode_tiles`` counts follow the batch's lengths, and the
``serve.*`` profiler spans nest on the host's trace under a caller's
span.  Timings are compared only with the spans that hold them, never
with fixed ratios.
"""
import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core import use
from repro.core.machine import TPU_V5E
from repro.models import LanguageModel
from repro.models.attention import PageSpec
from repro.runtime.batching import (ContinuousBatchingEngine, Request,
                                    poisson_trace)

KEYS = {"admission", "prefill", "grow", "eviction", "tables", "decode",
        "decode.dispatch", "decode.emit", "queue_wait",
        "expert_rows.decode", "experts_hit.decode", "expert_rows.prefill",
        "experts_hit.prefill", "decode_tiles.live", "decode_tiles.walked"}
TOP = ("admission", "prefill", "grow", "eviction", "tables", "decode")


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config(get_config("qwen3-0.6b"))
    return cfg, LanguageModel.init(jax.random.PRNGKey(0), cfg)


def _engine(model, slots, spec):
    cfg, params = model
    return ContinuousBatchingEngine(cfg, params, num_slots=slots, spec=spec)


def _request(rid, n=8, max_new=4):
    return Request(rid=rid, prompt=np.full(n, 3 + rid, np.int32),
                   max_new=max_new)


def _drain(eng):
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()


def test_every_key_present_before_the_first_step(model):
    eng = _engine(model, 2, PageSpec(24, 8, 6))
    assert set(eng.phase_seconds) == KEYS
    assert all(v == 0.0 for v in eng.phase_seconds.values())


def test_a_phase_leaves_out_the_phases_nested_in_it(model):
    eng = _engine(model, 2, PageSpec(24, 8, 6))
    ph = eng.phase_seconds
    t0 = time.perf_counter()
    with eng._phase("grow"):
        with eng._phase("eviction"):
            time.sleep(0.01)
    outer = time.perf_counter() - t0
    assert ph["eviction"] > 0 and ph["grow"] >= 0
    assert ph["grow"] + ph["eviction"] <= outer
    # A dotted key is a part of its phase and counts inside it.
    with eng._phase("decode"):
        with eng._phase("decode.emit"):
            time.sleep(0.01)
    assert 0 < ph["decode.emit"] <= ph["decode"]


def test_phases_fit_inside_their_parents(model):
    eng = _engine(model, 2, PageSpec(24, 8, 6))
    for r in range(3):
        eng.submit(_request(r))
    inside = 0.0
    while eng.queue or any(s is not None for s in eng.slots):
        t0 = time.perf_counter()
        eng.step()
        inside += time.perf_counter() - t0
    ph = eng.phase_seconds
    assert all(v >= 0 for v in ph.values())
    assert ph["decode.dispatch"] + ph["decode.emit"] <= ph["decode"]
    for k in ("admission", "prefill", "tables", "decode", "decode.dispatch",
              "decode.emit", "queue_wait"):
        assert ph[k] > 0, k
    # The top-level phases never overlap, so they fit inside step().
    assert sum(ph[k] for k in TOP) <= inside


@pytest.mark.parametrize("step_overhead_s,block,tiles", [
    (None, 48, 2),   # the v5e model: a step stages all 6 pages a slot has
    (0.0, 8, 12),    # no per-step overhead to cover: a page a step
])
def test_decode_tile_counts_follow_the_batch(model, step_overhead_s, block,
                                             tiles):
    """Each decode step adds the walk's grid rows to ``walked`` and, to
    ``live``, one row for every ``block`` tokens or part of them that an
    active slot attends to (its length after the step's write)."""
    machine = (None if step_overhead_s is None else dataclasses.replace(
        TPU_V5E, step_overhead_s=step_overhead_s))
    with use(machine=machine):
        eng = _engine(model, 2, PageSpec(24, 8, 6))
    eng.submit(_request(0, n=8, max_new=6))
    eng.submit(_request(1, n=20, max_new=6))
    steps, live = 0, 0
    while eng.queue or any(s is not None for s in eng.slots):
        if eng.step():
            steps += 1
            # every slot still held decoded this step
            live += sum(-(-int(eng.lengths[i]) // block)
                        for i, s in enumerate(eng.slots) if s is not None)
    ph = eng.phase_seconds
    assert steps > 0 and ph["decode_tiles.walked"] == steps * tiles
    assert ph["decode_tiles.live"] == live <= ph["decode_tiles.walked"]


def test_queue_wait_of_a_request_held_behind_another(model):
    eng = _engine(model, 1, PageSpec(24, 8, 6))
    eng.submit(_request(0))
    eng.submit(_request(1))
    while eng.queue:
        before = dict(eng.phase_seconds)
        eng.step()
    # The second request waited through the first one's prefill and
    # every decode step before its own admission.
    assert eng.phase_seconds["queue_wait"] > 0
    assert eng.phase_seconds["queue_wait"] >= (before["prefill"]
                                               + before["decode"])
    _drain(eng)


def test_a_readmission_waits_from_its_eviction(model):
    cfg, _ = model
    eng = _engine(model, 3, PageSpec(9, 4, 8))
    admitted = []
    admit = eng._admit

    def record(seq, slot):
        admitted.append((seq.req.rid, bool(seq.generated), seq.t_queued,
                         time.perf_counter() - seq.t_queued))
        admit(seq, slot)

    eng._admit = record
    for r in poisson_trace(num_requests=4, rate=2.0, prompt_lens=10,
                           max_new=8, vocab_size=cfg.vocab_size, seed=1):
        eng.submit(r)
    _drain(eng)
    readmits = [a for a in admitted if a[1]]
    assert eng.evictions > 0 and len(readmits) == eng.evictions
    assert len(admitted) == 4 + eng.evictions
    first = {rid: t for rid, again, t, _ in admitted if not again}
    # The stamp moved to the eviction, after the request's submission.
    assert all(t > first[rid] for rid, _, t, _ in readmits)
    # Every admission's wait, re-admissions too, is in the counter.
    assert eng.phase_seconds["queue_wait"] >= sum(a[3] for a in admitted)


def _host_events(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    return out


def test_spans_nest_on_the_profiler_clock(model, tmp_path):
    eng = _engine(model, 2, PageSpec(24, 8, 6))
    for r in range(2):
        eng.submit(_request(r, max_new=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.queue or any(s is not None for s in eng.slots):
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))

    def spans(name):
        found = [(s, e) for n, s, e in events if n == name]
        assert found, name
        return found

    def inside(child, parent):
        outer = spans(parent)
        for s, e in spans(child):
            assert any(ps <= s and e <= pe for ps, pe in outer), \
                f"{child} at {s} outside every {parent}"

    inside("serve.step", "bench.step")
    inside("serve.admission", "serve.step")
    inside("serve.prefill", "serve.admission")
    inside("serve.decode", "serve.step")
    for part in ("dispatch", "sync", "emit"):
        inside(f"serve.decode.{part}", "serve.decode")
