"""Empirical plan autotuner + persistent tuning cache (DESIGN.md §7).

The analytical planners in :mod:`repro.core.blocking` get close; this
module wins the last mile the way "Demystifying ARM SME" does — by
*timing* the machine-legal candidate tilings instead of trusting the cost
model.  ``search`` takes the top-K candidates ranked by the model
(:func:`repro.core.blocking.candidate_plans`), runs each through the
family executor's BUILD/RUN stages on the real operands, and returns the
measured winner with ``plan_source="autotuned"``.

Winners persist in an on-disk JSON :class:`TuningCache` keyed by
``(machine.name, desc.cache_key())`` so a process restart is a warm
start: ``engine.dispatch`` consults the cache *before* autotuning, and a
populated cache means zero timing runs.  A corrupt or missing cache file
degrades to an empty cache — the engine then falls through to the
autotune or analytical tier, never to an error.

The three-tier resolution policy (tuned cache → autotune → analytical
model) lives in :func:`repro.core.engine.dispatch`; this module owns only
the search and the persistence.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import threading
import time
import warnings
from typing import Any, Dict, Optional, Tuple

import jax

from .blocking import (BlockingPlan, FlashPlan, GroupedGemmPlan, Region,
                       SsdChunkPlan, TransposePlan, candidate_plans)
from .descriptor import KernelDescriptor
from .machine import MachineModel

TUNING_CACHE_VERSION = 1

# Timing discipline per candidate: one untimed call (trace + build), then
# ``_TIME_ITERS`` timed calls; the candidate's score is the minimum (least
# noise-contaminated) run.  Winners persist in the tuning cache, so a
# noisy measurement gets locked in — three iterations is the floor that
# keeps one scheduler hiccup from deciding a cache entry's lifetime.
_TIME_ITERS = 3


# ---------------------------------------------------------------------------
# Plan <-> JSON records
# ---------------------------------------------------------------------------

def _desc_dtypes(desc: KernelDescriptor) -> list:
    """The full dtype identity of one descriptor — every dtype-ish field
    plus the quant spec — recorded alongside cached knobs and re-checked
    on replay.  The descriptor's ``cache_key()`` already separates these
    (``dataclasses.astuple`` recurses into the nested ``QuantSpec``), so
    this is a belt-and-braces guard: a record written under a different
    keying scheme (or hand-edited) can never replay a wide plan onto a
    quantized problem or vice versa."""
    vals = []
    for attr in ("in_dtype", "acc_dtype", "out_dtype", "dtype"):
        v = getattr(desc, attr, None)
        if v is not None:
            vals.append(f"{attr}={v}")
    vals.append(f"quant={getattr(desc, 'quant', None)!r}")
    return vals


def plan_to_record(plan: Any) -> Dict[str, Any]:
    """Serialize one plan's tiling knobs (the descriptor is the cache key,
    so only the knobs travel — plus the dtype fingerprint as a replay
    guard)."""
    if isinstance(plan, BlockingPlan):
        rec = {"family": "gemm",
               "regions": [[r.row0, r.col0, r.rows, r.cols, r.bm, r.bn]
                           for r in plan.regions],
               "bk": plan.bk, "heterogeneous": plan.heterogeneous,
               "fused": plan.fused}
        if plan.comm is not None:
            rec["comm"] = plan.comm  # mesh strategy (DESIGN.md §14)
    elif isinstance(plan, FlashPlan):
        rec = {"family": "flash_attention",
               "block_q": plan.block_q, "block_k": plan.block_k,
               "fused": plan.fused}
    elif isinstance(plan, GroupedGemmPlan):
        rec = {"family": "grouped_gemm",
               "bm": plan.bm, "bk": plan.bk, "bn": plan.bn,
               "fused": plan.fused}
        if plan.comm is not None:
            rec["comm"] = plan.comm  # mesh strategy (DESIGN.md §14)
    elif isinstance(plan, TransposePlan):
        rec = {"family": "transpose", "bt": plan.bt}
    elif isinstance(plan, SsdChunkPlan):
        rec = {"family": "ssd_chunk", "fits_vmem": plan.fits_vmem,
               "fused": plan.fused}
    else:
        raise TypeError(f"unknown plan type: {type(plan).__name__}")
    rec["dtypes"] = _desc_dtypes(plan.desc)
    return rec


def plan_from_record(desc: KernelDescriptor,
                     record: Dict[str, Any]) -> Optional[Any]:
    """Rebuild a plan from its cached knobs; ``None`` on any mismatch
    (wrong family, malformed knobs) so callers degrade to re-planning."""
    try:
        family = record["family"]
        if family != desc.family:
            return None
        # Dtype fingerprint guard (pre-guard records lack it: accept —
        # their entry key was already dtype-separated via cache_key()).
        want = record.get("dtypes")
        if want is not None and list(want) != _desc_dtypes(desc):
            return None
        if family == "gemm":
            regions = tuple(Region(*map(int, r)) for r in record["regions"])
            # Pre-fusion cache entries lack "fused": replay them on the
            # multi-launch path they were actually timed on.
            return BlockingPlan(desc, regions, int(record["bk"]),
                                bool(record["heterogeneous"]),
                                fused=bool(record.get("fused", False)),
                                plan_source="autotuned",
                                comm=record.get("comm"))
        if family == "flash_attention":
            # Pre-schedule cache entries lack "fused": replay them on the
            # dense-grid path they were actually timed on.
            return FlashPlan(desc, int(record["block_q"]),
                             int(record["block_k"]),
                             fused=bool(record.get("fused", False)),
                             plan_source="autotuned")
        if family == "grouped_gemm":
            # Pre-schedule cache entries lack "fused": replay them on the
            # pad/scatter path they were actually timed on.
            return GroupedGemmPlan(desc, int(record["bm"]), int(record["bk"]),
                                   int(record["bn"]),
                                   fused=bool(record.get("fused", False)),
                                   plan_source="autotuned",
                                   comm=record.get("comm"))
        if family == "transpose":
            return TransposePlan(desc, int(record["bt"]),
                                 plan_source="autotuned")
        if family == "ssd_chunk":
            # Pre-schedule cache entries lack "fused": replay them on the
            # diag-kernel + XLA-scan path they were actually timed on.
            return SsdChunkPlan(desc, bool(record["fits_vmem"]),
                                fused=bool(record.get("fused", False)),
                                plan_source="autotuned")
        return None
    except (KeyError, TypeError, ValueError):
        return None


def _mode(interpret: bool) -> str:
    return "interpret" if interpret else "compiled"


def _entry_key(machine_name: str, desc: KernelDescriptor,
               interpret: bool) -> str:
    # desc.cache_key() is a tuple of ints/strings/bools/None; its repr is
    # stable and human-greppable in the JSON file.  The execution mode is
    # part of the key: a winner timed under interpret-mode emulation says
    # nothing about compiled execution and must never be replayed there.
    # Deliberately keyed by ``machine.tuning_key`` (name + network-
    # calibration provenance), not constants-fingerprint — measured
    # winners should survive run-to-run probe drift on one host, but a
    # network-calibrated host's mesh winners must never serve an
    # uncalibrated one (DESIGN.md §14).
    return f"{machine_name}|{_mode(interpret)}|{desc.cache_key()!r}"


# ---------------------------------------------------------------------------
# Persistent tuning cache
# ---------------------------------------------------------------------------

class TuningCache:
    """On-disk JSON store of autotuned winners, mirrored in memory.

    File format (DESIGN.md §7)::

        {"version": 1,
         "entries": {"<machine>|<desc-cache-key-repr>":
                     {"family": ..., <knobs...>, "us": <measured>}}}

    Loads are lazy and fault-tolerant: a missing file is an empty cache, a
    corrupt file warns once and is treated as empty (the next ``store``
    rewrites it whole).  Writes are atomic (tempfile + ``os.replace``).
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._load()

    def _load(self):
        try:
            with open(self.path) as f:
                data = json.load(f)
            if not isinstance(data, dict) or "entries" not in data:
                raise ValueError("not a tuning-cache file")
            entries = data["entries"]
            if not isinstance(entries, dict):
                raise ValueError("entries must be an object")
            self._entries = entries
        except FileNotFoundError:
            self._entries = {}
        except (json.JSONDecodeError, ValueError, OSError) as e:
            warnings.warn(f"ignoring corrupt tuning cache {self.path}: {e}")
            self._entries = {}

    def lookup(self, machine_name: str, desc: KernelDescriptor, *,
               interpret: bool) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._entries.get(
                _entry_key(machine_name, desc, interpret))

    def store(self, machine_name: str, desc: KernelDescriptor, plan: Any,
              measured_us: float, *, interpret: bool):
        record = plan_to_record(plan)
        record["us"] = round(float(measured_us), 3)
        # Wall-clock stamp: the fleet-merge CLI (tools/tune.py) unions
        # caches with newest-timing-wins, arbitrated by this field.
        record["ts"] = round(time.time(), 3)
        with self._lock:
            self._entries[_entry_key(machine_name, desc, interpret)] = record
            self._flush_locked()

    def _flush_locked(self):
        payload = {"version": TUNING_CACHE_VERSION, "entries": self._entries}
        d = os.path.dirname(os.path.abspath(self.path)) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tuning.tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# Path -> TuningCache.  One mirror per file per process; dropped by
# ``reset_tuning_caches`` (tests use that to simulate a cold process that
# re-reads the file).
_CACHES: Dict[str, TuningCache] = {}
_caches_lock = threading.Lock()


def get_tuning_cache(path: str) -> TuningCache:
    """The process-wide :class:`TuningCache` mirror for one file path
    (created on first use, shared after)."""
    key = os.path.abspath(path)
    with _caches_lock:
        cache = _CACHES.get(key)
        if cache is None:
            cache = _CACHES[key] = TuningCache(path)
        return cache


def reset_tuning_caches():
    """Drop all in-memory mirrors (files stay; next use reloads them)."""
    with _caches_lock:
        _CACHES.clear()


# ---------------------------------------------------------------------------
# Empirical search
# ---------------------------------------------------------------------------

def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


def can_autotune(operands: tuple, kw: Dict[str, Any]) -> bool:
    """Timing needs concrete arrays: under ``jit`` tracing the operands
    are tracers and wall-clock is meaningless — skip to the model tier."""
    vals = list(operands) + [v for v in kw.values() if v is not None]
    return all(_is_concrete(v) for v in vals)


def _time_plan(execute, desc, plan, operands, interpret: bool,
               kw: Dict[str, Any]) -> float:
    """Seconds for one candidate via the family's BUILD/RUN stages."""
    jax.block_until_ready(
        execute(desc, plan, *operands, interpret=interpret, **kw))
    best = float("inf")
    for _ in range(_TIME_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(
            execute(desc, plan, *operands, interpret=interpret, **kw))
        best = min(best, time.perf_counter() - t0)
    return best


def search(execute, desc: KernelDescriptor, machine: MachineModel,
           operands: tuple, kw: Dict[str, Any], *, interpret: bool,
           budget: int,
           tuning_cache: Optional[TuningCache] = None
           ) -> Tuple[Optional[Any], int]:
    """Time the top-``budget`` candidates; return (winner, timed_count).

    The winner carries ``plan_source="autotuned"`` and is persisted to
    ``tuning_cache`` when one is given.  Under the interpreter a
    candidate whose build or run raises is skipped, and if every candidate
    fails the caller falls back to the analytical tier (winner ``None``).
    Compiled (on a chip), a failure raises: skipping a kernel the chip's
    compiler refused would hide it behind whichever plan still builds.
    """
    candidates = candidate_plans(desc, machine, top_k=budget)
    # A forced execution-path override (config.fused="on"/"off") makes the
    # executor ignore the candidate's ``fused`` bit, so the two lowerings
    # of one region cover would be timed on the identical path and an
    # *untimed* fused bit could be persisted.  Keep only candidates whose
    # bit matches the path that will actually run (DESIGN.md §8).
    from .config import get_config
    mode = get_config().fused
    if mode != "auto":
        want = mode == "on"
        candidates = [c for c in candidates
                      if getattr(c, "fused", want) == want]
    if len(candidates) < 2:
        # Nothing to choose between (e.g. ssd_chunk has no free knobs):
        # timing would cost real executions with no decision to make, and
        # the analytical tier returns the same plan.
        return None, 0
    best_plan, best_t, timed = None, float("inf"), 0
    for plan in candidates:
        try:
            t = _time_plan(execute, desc, plan, operands, interpret, kw)
        except Exception as e:
            if not interpret:
                raise
            warnings.warn(f"autotune candidate failed for {desc.family}: {e}")
            continue
        timed += 1
        if t < best_t:
            best_plan, best_t = plan, t
    if best_plan is None:
        return None, timed
    best_plan = dataclasses.replace(best_plan, plan_source="autotuned")
    if tuning_cache is not None:
        # Keyed by ``tuning_key`` (name + network-calibration provenance,
        # DESIGN.md §14): records from network-calibrated and uncalibrated
        # hosts never serve each other even when they share a name.
        tuning_cache.store(machine.tuning_key, desc, best_plan, best_t * 1e6,
                           interpret=interpret)
    return best_plan, timed
