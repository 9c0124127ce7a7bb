"""Descriptor-driven kernel engine — registry, planning and dispatch.

The paper's pipeline is descriptor -> blocking plan -> generated kernel ->
dispatch cache (the LIBXSMM architecture, §IV).  This module generalizes
that pipeline from the dense-GEMM family to every kernel family in the
system.  A family is registered with two callables:

  * ``planner(desc, machine) -> plan`` — machine-model-driven tile
    selection (``repro.core.blocking``);
  * ``execute(desc, plan, *operands, interpret=...) -> result`` — runs the
    (cached) shape-specialized kernel build for that plan.

``dispatch(desc, *operands)`` is the single entry point: it resolves the
ambient :mod:`~repro.core.config`, serves the plan from an LRU plan cache
(planning used to re-run on *every* call — only kernel builds were
memoized), and invokes the family executor, which in turn serves kernel
builds from the LRU kernel cache.  Both caches key off
``desc.cache_key()`` — no family hand-writes a cache-key tuple — and both
expose per-family hit/miss/eviction stats (``stats()``).

A plan-cache miss resolves through a three-tier policy (DESIGN.md §7):

  1. **tuned cache** — the on-disk JSON store of previously autotuned
     winners (``config.tuning_cache``); a warm cache means a process
     restart re-plans nothing and times nothing;
  2. **autotune** — when ``config.autotune`` is set and the operands are
     concrete, time the top-K model-ranked candidates for real
     (:mod:`repro.core.autotune`) and persist the winner;
  3. **analytical model** — the family planner ranked by the machine
     model, as before.

Which tier served each resolution is visible per family in ``stats()``
(``plan_source_{tuned_cache,autotuned,model}``, ``autotune_timings``) and
on the plan itself (``plan.plan_source``).

Families self-register at import time; ``dispatch`` lazily imports the
owning ``kernels/<family>/ops`` module on first use, so ``repro.core``
never statically depends on ``repro.kernels`` (DESIGN.md §1).
"""
from __future__ import annotations

import dataclasses
import importlib
import threading
import warnings
from typing import Any, Callable, Dict, Iterable, List, Optional

from . import autotune as _autotune
from .config import get_config, resolve_interpret
from .descriptor import KernelDescriptor
from .jit_cache import GLOBAL_KERNEL_CACHE, LruCache
from .machine import MachineModel


@dataclasses.dataclass(frozen=True)
class Family:
    """One registered kernel family."""

    name: str
    planner: Callable[[KernelDescriptor, MachineModel], Any]
    execute: Callable[..., Any]  # (desc, plan, *operands, interpret=...)


_REGISTRY: Dict[str, Family] = {}
_registry_lock = threading.Lock()

# family name -> module that registers it (imported lazily on first use)
_FAMILY_MODULES = {
    "gemm": "repro.kernels.gemm.ops",
    "flash_attention": "repro.kernels.flash_attention.ops",
    "flash_attention_bwd": "repro.kernels.flash_attention.ops",
    "flash_decode": "repro.kernels.flash_attention.ops",
    "grouped_gemm": "repro.kernels.grouped_gemm.ops",
    "grouped_gemm_bwd": "repro.kernels.grouped_gemm.ops",
    "ssd_chunk": "repro.kernels.ssd_chunk.ops",
    "ssd_chunk_bwd": "repro.kernels.ssd_chunk.ops",
    "transpose": "repro.kernels.transpose.ops",
}

# desc -> plan.  Sized for the shape population of a whole model zoo; a
# plan is a few hundred bytes, so 64k entries is still tiny.
PLAN_CACHE = LruCache(max_entries=65536)

# Planner invocation counter per family (distinct from plan-cache misses
# only when callers bypass the cache with an explicit plan).
_plan_calls: Dict[str, int] = {}
_plan_calls_lock = threading.Lock()

# Three-tier resolution observability (DESIGN.md §7): which tier served
# each plan-cache miss, and how many candidate executions autotuning timed.
PLAN_SOURCES = ("tuned_cache", "autotuned", "model")
_plan_sources: Dict[str, Dict[str, int]] = {}
_autotune_timings: Dict[str, int] = {}

# Traced pallas_call launches per family (DESIGN.md §8): each family
# executor reports how many kernel launches one execute() emits — the
# fused GEMM path reports exactly 1 where the multi-launch path reports
# one per plan region.  Counted at trace/execute time, so a jit-compiled
# repeat call (which never re-enters Python) does not re-count.
# ``_fused_launches`` counts the share that ran a fused single-launch
# lowering, so a run can show which lowering it dispatched.
_launches: Dict[str, int] = {}
_fused_launches: Dict[str, int] = {}

# Explicit collectives issued per family (DESIGN.md §14): the distributed
# mesh strategies report the payload bytes and collective launches they
# emit around the per-shard kernel; the gathered strategy issues none
# (any weight resharding is XLA-implicit), so non-zero counters here mean
# a distributed execution really happened.  Trace-time counts, like
# ``_launches``.
_comm_bytes: Dict[str, int] = {}
_collective_launches: Dict[str, int] = {}

# AOT warm-start (DESIGN.md §15): every dispatch records its descriptor
# (keyed by cache key — one entry per distinct problem) so a serving
# process can save the population it actually served (``save_manifest``)
# and the next start can pre-resolve plans + pre-build kernels for it
# (``warmup``) before the first request arrives.
_seen_descs: Dict[tuple, KernelDescriptor] = {}
_warmups: Dict[str, int] = {}


def _note_source(family: str, source: str):
    with _plan_calls_lock:
        bucket = _plan_sources.setdefault(family,
                                          {s: 0 for s in PLAN_SOURCES})
        bucket[source] += 1


def _note_timings(family: str, n: int):
    with _plan_calls_lock:
        _autotune_timings[family] = _autotune_timings.get(family, 0) + n


def count_launches(family: str, n: int = 1, *, fused: bool = False):
    """Family executors call this once per execute() with the number of
    kernel launches they are about to emit (``stats()["…"]["launches"]``),
    and ``fused=True`` when they run a fused single-launch lowering
    (also counted under ``"launches_fused"``)."""
    with _plan_calls_lock:
        _launches[family] = _launches.get(family, 0) + n
        if fused:
            _fused_launches[family] = _fused_launches.get(family, 0) + n


def count_comm(family: str, nbytes: int, launches: int = 1):
    """Mesh executors call this with the per-device payload bytes and
    number of explicit collectives one execute() emits
    (``stats()["…"]["comm_bytes"]`` / ``["collective_launches"]``)."""
    with _plan_calls_lock:
        _comm_bytes[family] = _comm_bytes.get(family, 0) + int(nbytes)
        _collective_launches[family] = (
            _collective_launches.get(family, 0) + launches)


def register_family(name: str, planner, execute) -> Family:
    """Register (or replace) a kernel family.  Called at ops-module import."""
    fam = Family(name=name, planner=planner, execute=execute)
    with _registry_lock:
        _REGISTRY[name] = fam
    return fam


def get_family(name: str) -> Family:
    """Resolve a family by name, lazily importing its registering ops
    module on first use (the only core → kernels seam, DESIGN.md §1)."""
    fam = _REGISTRY.get(name)
    if fam is None:
        module = _FAMILY_MODULES.get(name)
        if module is None:
            raise KeyError(f"unknown kernel family {name!r}; "
                           f"known: {sorted(_FAMILY_MODULES)}")
        importlib.import_module(module)  # side effect: register_family()
        fam = _REGISTRY.get(name)
        if fam is None:
            raise RuntimeError(f"module {module} did not register family "
                               f"{name!r}")
    return fam


def families() -> Dict[str, Family]:
    """Snapshot of the currently registered kernel families."""
    with _registry_lock:
        return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Planning
# ---------------------------------------------------------------------------

def _resolve_plan(desc: KernelDescriptor, cfg, *,
                  machine: Optional[MachineModel] = None,
                  operands: Optional[tuple] = None,
                  kw: Optional[dict] = None,
                  interpret: Optional[bool] = None) -> Any:
    """Plan-cache lookup; a miss walks the three tiers (DESIGN.md §7)."""
    fam = get_family(desc.family)
    machine = machine or cfg.machine_model
    interpret = resolve_interpret(cfg.interpret if interpret is None
                                  else interpret)
    kw = kw or {}
    # Timing needs concrete operands: under jit tracing (or from plan_for,
    # which has no operands) the autotune tier is unavailable.
    autotunable = (cfg.autotune and operands is not None
                   and _autotune.can_autotune(operands, kw))
    tier = "autotune" if autotunable else \
        ("tuned" if (cfg.tuning_cache or cfg.tuning_cache_preload)
         else "model")
    # The key names the machine by name AND constants-fingerprint (two
    # calibrations of one host share a name but not plans) and the
    # resolution policy (so e.g. a model-tier plan cached during jit
    # tracing never masks a later concrete-operand autotune).
    key = desc.cache_key() + ("plan", machine.name, machine.fingerprint,
                              tier, cfg.tuning_cache or "",
                              cfg.tuning_cache_preload or "")

    def build_plan():
        # Tier 1: persistent tuned cache — a warm file re-times nothing.
        # Lookups key by ``machine.tuning_key`` (name + network-calibration
        # provenance, DESIGN.md §14) so records from network-calibrated
        # and uncalibrated hosts never serve each other.  The read-only
        # preload file (``configure(tuning_cache_preload=)``, fleet-merged
        # by tools/tune.py) is the fallback behind the writable cache.
        for path in (cfg.tuning_cache, cfg.tuning_cache_preload):
            if not path:
                continue
            cache = _autotune.get_tuning_cache(path)
            record = cache.lookup(machine.tuning_key, desc,
                                  interpret=interpret)
            if record is not None:
                plan = _autotune.plan_from_record(desc, record)
                if plan is not None:
                    _note_source(desc.family, "tuned_cache")
                    return plan
        # Tier 2: budgeted empirical search over the model-ranked top-K.
        if autotunable:
            cache = (_autotune.get_tuning_cache(cfg.tuning_cache)
                     if cfg.tuning_cache else None)
            plan, timed = _autotune.search(
                fam.execute, desc, machine, operands, kw,
                interpret=interpret, budget=cfg.autotune_budget,
                tuning_cache=cache)
            _note_timings(desc.family, timed)
            if plan is not None:
                _note_source(desc.family, "autotuned")
                if cfg.tuning_cache:
                    # Overwrite the tuned-tier entry too: a jit trace that
                    # resolved before the file was populated may have
                    # cached a model plan there, and get_or_build would
                    # keep serving it for the rest of the process.
                    PLAN_CACHE.put(
                        desc.cache_key() + ("plan", machine.name,
                                            machine.fingerprint, "tuned",
                                            cfg.tuning_cache or "",
                                            cfg.tuning_cache_preload or ""),
                        plan)
                return plan
        # Tier 3: analytical machine-model planner.
        with _plan_calls_lock:
            _plan_calls[desc.family] = _plan_calls.get(desc.family, 0) + 1
        _note_source(desc.family, "model")
        return fam.planner(desc, machine)

    return PLAN_CACHE.get_or_build(key, build_plan)


def plan_for(desc: KernelDescriptor,
             machine: Optional[MachineModel] = None) -> Any:
    """Plan cache lookup: (descriptor, machine) -> family plan.

    No operands, so the autotune tier is skipped; the tuned cache (when
    configured) and the analytical model still apply.
    """
    return _resolve_plan(desc, get_config(), machine=machine)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def dispatch(desc: KernelDescriptor, *operands, plan: Any = None,
             interpret: Optional[bool] = None, **kw) -> Any:
    """Run one kernel request through the engine.

    ``plan=None`` resolves via tuned-cache → autotune → analytical-model
    (DESIGN.md §7), behind the plan cache; an explicit plan (benchmark
    sweeps, tests pinning tile sizes) bypasses all of it.  ``interpret``
    defaults from the ambient config — no per-call plumbing — and is
    resolved against the platform
    (:func:`~repro.core.config.resolve_interpret`).
    """
    fam = get_family(desc.family)
    cfg = get_config()
    _seen_descs.setdefault(desc.cache_key(), desc)
    interpret = resolve_interpret(cfg.interpret if interpret is None
                                  else interpret)
    if plan is None:
        plan = _resolve_plan(desc, cfg, operands=operands, kw=kw,
                             interpret=interpret)
    return fam.execute(desc, plan, *operands, interpret=interpret, **kw)


# ---------------------------------------------------------------------------
# AOT warm-start (DESIGN.md §15)
# ---------------------------------------------------------------------------

def seen_descriptors() -> List[KernelDescriptor]:
    """Every distinct descriptor dispatched since the last full reset,
    in deterministic (cache-key) order — the recordable population a
    warm-start manifest captures."""
    return [_seen_descs[k] for k in sorted(_seen_descs, key=repr)]


def save_manifest(path: str,
                  descriptors: Optional[Iterable[KernelDescriptor]] = None
                  ) -> int:
    """Record a descriptor manifest for ``warmup`` (default: everything
    this process dispatched, :func:`seen_descriptors`).  Returns the
    number of entries written."""
    from . import warmstart as _warmstart
    descs = list(descriptors) if descriptors is not None \
        else seen_descriptors()
    return _warmstart.save_manifest(path, descs)


def warmup(descriptors: Optional[Iterable[KernelDescriptor]] = None, *,
           manifest: Optional[str] = None, build: bool = True,
           interpret: Optional[bool] = None) -> Dict[str, int]:
    """Pre-resolve plans and pre-build kernels before the first request.

    The AOT warm-start entry point (DESIGN.md §15): for each descriptor —
    given directly, loaded from a ``manifest`` path, or defaulted from
    ``configure(warm_start=...)`` / ``REPRO_WARM_START`` — resolve its
    plan through the normal three tiers (no operands, so the autotune
    tier is skipped: a preloaded tuning cache serves the tuned tier and
    times nothing) and, with ``build=True``, execute the family once on
    synthesized zero operands so the kernel cache is hot.  After a
    ``reset_stats(entries=False)`` a warmed serving step then shows
    ``autotune_timings == 0`` and zero plan-cache misses.

    Returns ``{family: warmed descriptor count}``; the same counts
    accumulate in ``stats()`` under ``"warmups"``.  A descriptor warmup
    cannot synthesize operands for (e.g. mesh descriptors) still warms
    its plan.  A failed build raises when kernels run compiled — it is a
    kernel the chip's compiler refused — and only warns under the
    interpreter, where the descriptor keeps its plan.
    """
    cfg = get_config()
    if descriptors is None:
        path = manifest if manifest is not None else cfg.warm_start
        if not path:
            raise ValueError(
                "warmup() needs descriptors, a manifest path, or "
                "configure(warm_start=...) / REPRO_WARM_START")
        from . import warmstart as _warmstart
        descriptors = _warmstart.load_manifest(path)
    interpret = resolve_interpret(cfg.interpret if interpret is None
                                  else interpret)
    counts: Dict[str, int] = {}
    for desc in descriptors:
        fam = get_family(desc.family)
        plan = _resolve_plan(desc, cfg, interpret=interpret)
        if build:
            from . import warmstart as _warmstart
            try:
                synth = _warmstart.synth_operands(desc)
                if synth is not None:
                    operands, kw = synth
                    fam.execute(desc, plan, *operands,
                                interpret=interpret, **kw)
            except Exception as e:
                if not interpret:
                    raise
                warnings.warn(
                    f"warmup build failed for {desc.family} "
                    f"{desc.cache_key()!r}: {e}")
        counts[desc.family] = counts.get(desc.family, 0) + 1
        with _plan_calls_lock:
            _warmups[desc.family] = _warmups.get(desc.family, 0) + 1
    return counts


def resolve_fused(plan: Any) -> bool:
    """Resolve a plan's execution path (DESIGN.md §9): the ambient
    ``config.fused`` override wins ("on"/"off"), else the ``fused`` bit
    the planner/autotuner set on the plan.  Shared by every family with a
    fused single-launch lowering (gemm, grouped_gemm)."""
    mode = get_config().fused
    if mode == "on":
        return True
    if mode == "off":
        return False
    return bool(getattr(plan, "fused", False))


def build_cached(key: tuple, builder: Callable[[], Any]) -> Any:
    """Kernel-cache helper for family executors.

    ``key`` must be descriptor-derived (``desc.cache_key() + knobs``) so
    the first element names the family for the per-family stats.
    """
    return GLOBAL_KERNEL_CACHE.get_or_build(key, builder)


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------

def stats() -> Dict[str, Dict[str, int]]:
    """Per-family engine stats across both cache layers.

    {family: {plan_hits, plan_misses, plan_evictions, planner_calls,
              plan_source_tuned_cache, plan_source_autotuned,
              plan_source_model, autotune_timings, launches,
              launches_fused, comm_bytes, collective_launches, warmups,
              kernel_hits, kernel_misses, kernel_evictions}}

    Backward families (``<family>_bwd`` descriptors, DESIGN.md §11) fold
    into their forward family's bucket under ``*_bwd``-suffixed keys
    (``launches_bwd``, ``plan_source_model_bwd``, ...), so one row tells
    the whole forward + backward story per family.
    """
    out: Dict[str, Dict[str, int]] = {}

    def bucket(fam: str) -> Dict[str, int]:
        return out.setdefault(fam, {
            **{k + sfx: 0 for sfx in ("", "_bwd") for k in (
                "plan_hits", "plan_misses", "plan_evictions",
                "planner_calls",
                *(f"plan_source_{s}" for s in PLAN_SOURCES),
                "autotune_timings", "launches", "launches_fused",
                "comm_bytes", "collective_launches", "warmups",
                "kernel_hits", "kernel_misses", "kernel_evictions")},
        })

    def slot(fam: str):
        """Bucket + key suffix: backward families report into the forward
        family's row under ``*_bwd`` keys."""
        if fam.endswith("_bwd"):
            return bucket(fam[:-4]), "_bwd"
        return bucket(fam), ""

    for fam, c in PLAN_CACHE.family_stats().items():
        b, sfx = slot(fam)
        b["plan_hits" + sfx] = c["hits"]
        b["plan_misses" + sfx] = c["misses"]
        b["plan_evictions" + sfx] = c["evictions"]
    with _plan_calls_lock:
        for fam, n in _plan_calls.items():
            b, sfx = slot(fam)
            b["planner_calls" + sfx] = n
        for fam, sources in _plan_sources.items():
            b, sfx = slot(fam)
            for s, n in sources.items():
                b[f"plan_source_{s}{sfx}"] = n
        for fam, n in _autotune_timings.items():
            b, sfx = slot(fam)
            b["autotune_timings" + sfx] = n
        for fam, n in _launches.items():
            b, sfx = slot(fam)
            b["launches" + sfx] = n
        for fam, n in _fused_launches.items():
            b, sfx = slot(fam)
            b["launches_fused" + sfx] = n
        for fam, n in _comm_bytes.items():
            b, sfx = slot(fam)
            b["comm_bytes" + sfx] = n
        for fam, n in _collective_launches.items():
            b, sfx = slot(fam)
            b["collective_launches" + sfx] = n
        for fam, n in _warmups.items():
            b, sfx = slot(fam)
            b["warmups" + sfx] = n
    for fam, c in GLOBAL_KERNEL_CACHE.family_stats().items():
        b, sfx = slot(fam)
        b["kernel_hits" + sfx] = c["hits"]
        b["kernel_misses" + sfx] = c["misses"]
        b["kernel_evictions" + sfx] = c["evictions"]
    return out


def reset_stats(*, entries: bool = True):
    """Reset all engine counters.

    ``entries=True`` (test isolation) also drops cached plans, built
    kernels, and the in-memory tuning-cache mirrors (on-disk files stay —
    a fresh mirror reloads them, which is how tests simulate a process
    restart).  ``entries=False`` (benchmark phase boundaries) zeroes the
    counters but keeps every cache warm, so per-phase tables don't charge
    one phase for another's builds.
    """
    if entries:
        PLAN_CACHE.clear()
        GLOBAL_KERNEL_CACHE.clear()
        _autotune.reset_tuning_caches()
        _seen_descs.clear()
    else:
        PLAN_CACHE.reset_stats()
        GLOBAL_KERNEL_CACHE.reset_stats()
    with _plan_calls_lock:
        _plan_calls.clear()
        _plan_sources.clear()
        _autotune_timings.clear()
        _launches.clear()
        _fused_launches.clear()
        _comm_bytes.clear()
        _collective_launches.clear()
        _warmups.clear()
