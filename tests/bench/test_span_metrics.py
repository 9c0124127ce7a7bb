"""CPU tests of the per-layer readers of the engine's phase spans and
queue counter: their arithmetic on a synthetic reading, nothing read
where the program keeps no such keys (as before the spans existed) or
where the window holds no step or admission, and the cells that report
each.

Run from the repository root:  python -m pytest -q tests/bench
"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402
from bench.serve import WindowWork  # noqa: E402

READERS = ("step.host_ms", "step.prefill_ms", "sched.queue_wait_ms")
PHASES = {"admission": 0.25, "prefill": 1.5, "grow": 0.125,
          "eviction": 0.0625, "tables": 0.5, "decode": 4.0,
          "decode.dispatch": 0.75, "decode.emit": 0.375, "queue_wait": 0.3}
# The keys a program kept before its phases had profiler spans.
OLD_PHASES = {"admission": 0.25, "prefill": 1.5, "decode": 4.0,
              "eviction": 0.0625}


def _reading(phase_s, steps=40, prefills=(256, 512, 64)):
    work = WindowWork(decode_contexts=[[300, 600]] * steps,
                      prefills=list(prefills), steps=steps)
    return run.Reading(dims={}, peaks={}, window_s=2.0, work=work,
                       phase_s=dict(phase_s), trace=None,
                       memory_peak_bytes=None)


@pytest.mark.parametrize("name,want", [
    ("step.host_ms", 1e3 * (0.25 + 0.125 + 0.0625 + 0.5 + 0.75 + 0.375) / 40),
    ("step.prefill_ms", 1e3 * 1.5 / 3),
    ("sched.queue_wait_ms", 1e3 * 0.3 / 3),
])
def test_reader_arithmetic(name, want):
    assert run.read_metric(name, _reading(PHASES)) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_nothing_read_without_the_keys(name):
    assert run.read_metric(name, _reading(OLD_PHASES)) is None
    assert run.read_metric(name, _reading({})) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_read_without_steps_or_admissions(name):
    assert run.read_metric(name, _reading(PHASES, steps=0,
                                          prefills=())) is None


@pytest.mark.parametrize("name", ("step.prefill_ms", "sched.queue_wait_ms"))
def test_nothing_read_without_admissions(name):
    assert run.read_metric(name, _reading(PHASES, prefills=())) is None


@pytest.mark.parametrize("cell,want", [
    ("qwen3-0.6b.chat", set(READERS)),
    ("qwen2.5-3b.prefill-backlog", {"step.host_ms", "step.prefill_ms"}),
])
def test_cells_that_report_each_reader(cell, want):
    names = {m["name"] for m in run.load_cell(cell).per_layer}
    assert names & set(READERS) == want
