"""Process-wide engine configuration: backend, interpret mode, machine.

The paper's dispatcher has one piece of ambient state — which lowering
serves a request (generated SME kernel vs vendor BLAS).  Ours has more:

  * ``backend``   — "xla" (dot_general, the vendor-BLAS analogue; default
                    in CPU containers) or "pallas" (the paper's engine:
                    descriptor → plan → generated kernel);
  * ``interpret`` — ``None`` (the default) derives it from the platform
                    at dispatch (:func:`resolve_interpret`): compiled on a
                    TPU, interpreted on the CPU test backend.  ``False``
                    pins compiled kernels (chip-compile tests on the CPU);
                    ``True`` is refused while a TPU is present;
  * ``machine``   — the :class:`~repro.core.machine.MachineModel` that
                    parameterizes every tile planner (the "Table I"
                    constants, or a microbench-calibrated model); ``None``
                    (the default) picks the model of the first device's
                    ``device_kind`` (:attr:`EngineConfig.machine_model`);
  * ``autotune``  — let ``engine.dispatch`` time the top-K candidate
                    tilings empirically instead of trusting the model
                    (DESIGN.md §7); ``autotune_budget`` caps K;
  * ``tuning_cache`` — path of the on-disk JSON tuning cache that makes
                    autotuned winners survive process restarts;
  * ``tuning_cache_preload`` — read-only fleet-merged tuning cache
                    (tools/tune.py) consulted after ``tuning_cache``
                    misses — the warm-start path (DESIGN.md §14);
  * ``warm_start`` — path of a recorded descriptor manifest
                    (``engine.save_manifest``); ``engine.warmup()`` with
                    no arguments replays it, pre-resolving plans and
                    pre-building kernels before the first request
                    (DESIGN.md §15);
  * ``fused``     — plan-execution policy for families with a fused
                    single-launch lowering (GEMM, grouped GEMM —
                    DESIGN.md §8/§9): "auto" follows the plan's ``fused``
                    bit (planner/autotuner choice), "on"/"off" force the
                    single-launch fused or the multi-launch / pad-scatter
                    lowering (``engine.resolve_fused``).

  * ``quant``     — ambient low-precision spec (DESIGN.md §13) applied by
                    the GEMM-family public entry points (``gemm``,
                    ``grouped_gemm``) when a call does not pass its own:
                    ``None`` (wide, the default), a
                    :class:`~repro.core.descriptor.QuantSpec`, or a
                    shorthand string (``"int8"``/``"w8a16"``/``"fp8"``).
                    Per call, ``quant=False`` opts out of the ambient
                    spec.

Env-var overrides seed the process default at import: ``REPRO_AUTOTUNE=1``,
``REPRO_TUNING_CACHE=/path/to/cache.json``,
``REPRO_TUNING_CACHE_PRELOAD=/path/to/fleet.json``,
``REPRO_AUTOTUNE_BUDGET=K``, ``REPRO_FUSED=auto|on|off``,
``REPRO_QUANT=int8|w8a16|fp8``, ``REPRO_WARM_START=/path/to/manifest.json``.

Configuration is layered: a process-wide default (``configure``) under a
thread-local override stack (``use`` context manager), so a serving thread
can pin ``backend="pallas"`` without racing a training thread.  This module
replaces the private ``_state`` that used to live in ``core.matmul`` and
the ``interpret=`` kwarg that every ``kernels/*/ops.py`` entry point
threaded through.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Optional

import jax

from .descriptor import QuantSpec, resolve_quant
from .machine import MachineModel, get_machine, machine_for_device

BACKENDS = ("xla", "pallas")
FUSED_MODES = ("auto", "on", "off")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """One immutable snapshot of the engine's ambient configuration."""

    backend: str = "xla"
    interpret: Optional[bool] = None
    machine: Optional[MachineModel] = None
    # Empirical plan search (DESIGN.md §7).  ``tuning_cache`` is a JSON
    # file path; empty string means "no cache" (``replace`` treats None as
    # "leave unchanged", so "" is the explicit off switch).
    autotune: bool = False
    autotune_budget: int = 8
    tuning_cache: Optional[str] = None
    # Read-only warm-start cache (DESIGN.md §14): a fleet-merged tuning
    # file (tools/tune.py merge) consulted after ``tuning_cache`` misses.
    # Never written — serving processes start with zero autotune stalls
    # without contending on the shared file.
    tuning_cache_preload: Optional[str] = None
    # AOT warm-start manifest (DESIGN.md §15): a recorded descriptor
    # population ``engine.warmup()`` replays with no arguments.  Empty
    # string = explicit off (``replace`` treats None as "leave
    # unchanged", matching ``tuning_cache`` semantics).
    warm_start: Optional[str] = None
    # Plan-execution policy for fused-capable families (DESIGN.md §8/§9):
    # "auto" honors the plan's fused bit; "on"/"off" force the
    # single-launch / multi-launch (or pad-scatter) lowering.
    fused: str = "auto"
    # Ambient quant spec for the GEMM-family entry points (DESIGN.md
    # §13); None = wide execution unless a call passes its own.
    quant: Optional[QuantSpec] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {self.backend!r}")
        if self.autotune_budget < 1:
            raise ValueError(f"autotune_budget must be >= 1, "
                             f"got {self.autotune_budget}")
        if self.fused not in FUSED_MODES:
            raise ValueError(f"fused must be one of {FUSED_MODES}, "
                             f"got {self.fused!r}")
        if self.quant is not None and not isinstance(self.quant, QuantSpec):
            raise ValueError(f"quant must be None or a QuantSpec, "
                             f"got {self.quant!r}")

    @property
    def machine_model(self) -> MachineModel:
        """The configured model, else the one for the first device
        (:func:`~repro.core.machine.machine_for_device`)."""
        if self.machine is not None:
            return self.machine
        return machine_for_device(jax.devices()[0])

    def replace(self, **kw) -> "EngineConfig":
        kw = {k: v for k, v in kw.items() if v is not None}
        if isinstance(kw.get("machine"), str):
            kw["machine"] = get_machine(kw["machine"])
        if "quant" in kw:
            # quant=False is the explicit off switch (None means "leave
            # unchanged", matching tuning_cache="" semantics).
            kw["quant"] = resolve_quant(kw["quant"])
            if kw["quant"] is None:
                return dataclasses.replace(
                    self, **{k: v for k, v in kw.items() if k != "quant"},
                    quant=None)
        return dataclasses.replace(self, **kw)


def resolve_interpret(setting: Optional[bool]) -> bool:
    """Whether Pallas kernels run in interpret mode: ``setting`` when
    given, else whether JAX's default backend is something other than a
    TPU.  A TPU is never timed through the interpreter: asking for
    interpret mode while one is present raises."""
    on_tpu = jax.default_backend() == "tpu"
    if setting is None:
        return not on_tpu
    if setting and on_tpu:
        raise ValueError("interpret mode is the CPU test backend's; "
                         "a TPU is present, so kernels run compiled")
    return setting


def _env_default() -> EngineConfig:
    # A malformed env var must not take down `import repro`: warn and
    # fall back to the field default instead.
    budget = EngineConfig.autotune_budget
    raw = os.environ.get("REPRO_AUTOTUNE_BUDGET")
    if raw:
        try:
            budget = int(raw)
            if budget < 1:
                raise ValueError("must be >= 1")
        except ValueError as e:
            import warnings
            warnings.warn(f"ignoring REPRO_AUTOTUNE_BUDGET={raw!r}: {e}")
            budget = EngineConfig.autotune_budget
    fused = os.environ.get("REPRO_FUSED", "").lower()
    if fused in ("1", "true", "yes"):
        fused = "on"
    elif fused in ("0", "false", "no"):
        fused = "off"
    if fused not in FUSED_MODES:
        if fused:
            import warnings
            warnings.warn(f"ignoring REPRO_FUSED={fused!r}: "
                          f"must be one of {FUSED_MODES}")
        fused = "auto"
    quant = None
    raw = os.environ.get("REPRO_QUANT", "").lower()
    if raw and raw not in ("0", "false", "no", "off", "none"):
        try:
            quant = resolve_quant(raw)
        except ValueError as e:
            import warnings
            warnings.warn(f"ignoring REPRO_QUANT={raw!r}: {e}")
    return EngineConfig(
        autotune=os.environ.get("REPRO_AUTOTUNE", "").lower()
        in ("1", "true", "yes", "on"),
        autotune_budget=budget,
        tuning_cache=os.environ.get("REPRO_TUNING_CACHE") or None,
        tuning_cache_preload=os.environ.get("REPRO_TUNING_CACHE_PRELOAD")
        or None,
        warm_start=os.environ.get("REPRO_WARM_START") or None,
        fused=fused,
        quant=quant,
    )


_DEFAULT = _env_default()
_default_lock = threading.Lock()
_tls = threading.local()


def _stack() -> list:
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def get_config() -> EngineConfig:
    """Effective config: innermost thread-local override, else the global."""
    stack = _stack()
    return stack[-1] if stack else _DEFAULT


def configure(*, backend: Optional[str] = None,
              interpret: Optional[bool] = None,
              machine=None, autotune: Optional[bool] = None,
              autotune_budget: Optional[int] = None,
              tuning_cache: Optional[str] = None,
              tuning_cache_preload: Optional[str] = None,
              warm_start: Optional[str] = None,
              fused: Optional[str] = None, quant=None) -> EngineConfig:
    """Mutate the process-wide default (all threads without an override)."""
    global _DEFAULT
    with _default_lock:
        _DEFAULT = _DEFAULT.replace(backend=backend, interpret=interpret,
                                    machine=machine, autotune=autotune,
                                    autotune_budget=autotune_budget,
                                    tuning_cache=tuning_cache,
                                    tuning_cache_preload=tuning_cache_preload,
                                    warm_start=warm_start,
                                    fused=fused, quant=quant)
        return _DEFAULT


@contextlib.contextmanager
def use(*, backend: Optional[str] = None, interpret: Optional[bool] = None,
        machine=None, autotune: Optional[bool] = None,
        autotune_budget: Optional[int] = None,
        tuning_cache: Optional[str] = None,
        tuning_cache_preload: Optional[str] = None,
        warm_start: Optional[str] = None,
        fused: Optional[str] = None, quant=None):
    """Thread-local override: ``with use(backend="pallas"): ...``."""
    stack = _stack()
    stack.append(get_config().replace(backend=backend, interpret=interpret,
                                      machine=machine, autotune=autotune,
                                      autotune_budget=autotune_budget,
                                      tuning_cache=tuning_cache,
                                      tuning_cache_preload=tuning_cache_preload,
                                      warm_start=warm_start,
                                      fused=fused, quant=quant))
    try:
        yield stack[-1]
    finally:
        stack.pop()


# ---------------------------------------------------------------------------
# Back-compat shims (the pre-engine ``core.matmul`` surface)
# ---------------------------------------------------------------------------

def set_backend(backend: str, interpret: Optional[bool] = None):
    """Legacy global setter — prefer :func:`configure` / :func:`use`."""
    configure(backend=backend, interpret=interpret)


def get_backend() -> str:
    """The effective backend name ("xla" or "pallas") — legacy accessor."""
    return get_config().backend


@contextlib.contextmanager
def backend(name: str, interpret: Optional[bool] = None):
    """Legacy context manager — alias of :func:`use`."""
    with use(backend=name, interpret=interpret) as cfg:
        yield cfg
