"""Machine model for the target accelerator (TPU v5e).

This is the "Table I" of the system: the hardware constants that the paper
derives by microbenchmarking M4's SME unit, we pin from the published TPU
v5e specifications. They feed two consumers:

  * the blocking planner (``repro.core.blocking``), which sizes VMEM
    accumulator blocks the way the paper sizes ZA register blockings, and
  * the roofline analysis (``repro.launch.roofline``), which converts
    compiled HLO FLOPs / bytes / collective bytes into seconds.

Models come from two sources, mirroring the paper's two phases:

  * **pinned** — the static Table-I constants below (``TPU_V5E``,
    ``CPU_HOST``), used when the target is not the host;
  * **calibrated** — :meth:`MachineModel.from_probes` folds
    ``repro.core.microbench`` probe results (matmul throughput per dtype,
    streaming bandwidth, per-dispatch overhead) into a copy of a base
    model, exactly like the paper's §III measurements parameterize the
    §IV code generator.  See DESIGN.md §7.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import warnings
from typing import Dict, Iterable, Mapping, Optional, Union

import jax.numpy as jnp

# Fixed cost (seconds) charged per microkernel/grid-step launch by every
# planner cost model (``repro.core.blocking``).  On TPU this models grid
# sequencing + pipeline refill; calibration replaces it with the measured
# dispatch latency.  The value only needs to rank plans, not predict
# wall-clock.
DEFAULT_STEP_OVERHEAD_S = 2.0e-7

# Fixed cost (seconds) charged per *kernel launch* (one ``pallas_call``
# dispatch: argument marshalling, grid setup, pipeline warm-up).  This is
# what the fused single-launch GEMM path (DESIGN.md §8) amortizes: a
# multi-launch plan pays it once per region, the fused plan exactly once.
DEFAULT_LAUNCH_OVERHEAD_S = 2.0e-6

# Refittable lowering-cost coefficients (DESIGN.md §15).  The seed values
# are the BENCH_gemm_fused.json calibration from ``repro.core.blocking``;
# an offline ``tools/tune.py refit`` replaces them (and the two dispatch
# overheads above) with a robust least-squares fit of the fleet's
# accumulated TuningCache timings.
DEFAULT_FUSED_TILE_DECODE_S = 6e-7  # per fused grid step: table decode
DEFAULT_EXTRA_LAUNCH_FACTOR = 0.25  # cost of each launch beyond the first
DEFAULT_STITCH_DISCOUNT = 0.25      # fraction of naive stitch bytes paid

# Version of the refit-model JSON emitted by ``tools/tune.py refit`` and
# consumed by :func:`load_refit_model`.
REFIT_MODEL_VERSION = 1

# Coefficients a refit model may carry.  :func:`load_refit_model` rejects
# files mentioning anything else: an unknown key means the file was
# written by a newer tool than this reader understands (the "stale
# reader" degradation path — fall back to the probe-only base).
REFIT_COEFFICIENTS = (
    "step_overhead_s", "launch_overhead_s", "extra_launch_factor",
    "fused_tile_decode_s", "stitch_discount",
    "ici_bandwidth_gbps", "collective_launch_s", "collective_efficiency",
)


@dataclasses.dataclass(frozen=True)
class MachineModel:
    """Performance model of one accelerator chip and its interconnect."""

    name: str
    # --- compute ---------------------------------------------------------
    # Peak MACs structured as (sublane, lane) native register tiling and the
    # systolic array dimensions.  SME analogue: SVL=512b => 16x16 fp32 ZA
    # tile; TPU v5e: 128x128 MXU.
    mxu_rows: int
    mxu_cols: int
    peak_flops: Dict[str, float]  # dtype name -> FLOP/s per chip
    # --- memory hierarchy -------------------------------------------------
    hbm_bytes: int
    hbm_bw: float  # bytes/s
    vmem_bytes: int
    # native register tile (second-minor, minor) granule per dtype
    sublanes: Dict[str, int]
    lanes: int
    # --- interconnect ------------------------------------------------------
    ici_bw_per_link: float  # bytes/s per ICI link
    ici_links: int  # links per chip in the 2D torus
    dcn_bw: float  # bytes/s per chip across pods
    # --- dispatch ----------------------------------------------------------
    # per-microkernel/grid-step launch overhead charged by plan cost models
    step_overhead_s: float = DEFAULT_STEP_OVERHEAD_S
    # per-pallas_call dispatch overhead (the cost the fused single-launch
    # path pays once and the multi-launch path pays per region)
    launch_overhead_s: float = DEFAULT_LAUNCH_OVERHEAD_S
    # --- calibrated network (DESIGN.md §14) --------------------------------
    # ``None`` means *not network-calibrated*: the interconnect probes did
    # not run (1-device host, or a pinned Table-I model).  The planner then
    # falls back to the pinned per-link aggregate and ``fingerprint`` /
    # ``tuning_key`` carry the provenance so tuned-cache records never mix
    # calibrated and uncalibrated machines.
    ici_bandwidth_gbps: Optional[float] = None  # measured all_gather GB/s
    collective_launch_s: Optional[float] = None  # per-collective launch cost
    # per-collective bandwidth efficiency relative to the all_gather probe,
    # e.g. {"all_gather": 1.0, "all_to_all": 0.7, "psum": 0.5}
    collective_efficiency: Optional[Dict[str, float]] = None
    # --- refittable lowering costs (DESIGN.md §15) -------------------------
    # Per fused grid step: tile-table decode + predication — what the
    # fused single-launch path pays instead of per-region dispatch.
    fused_tile_decode_s: float = DEFAULT_FUSED_TILE_DECODE_S
    # Cost of each kernel launch beyond the first, as a fraction of
    # ``launch_overhead_s`` (later launches reuse warm dispatch state).
    extra_launch_factor: float = DEFAULT_EXTRA_LAUNCH_FACTOR
    # Fraction of the naive stitch-traffic bytes the multi-launch path
    # really pays (operand slices + C assembly overlap with compute).
    stitch_discount: float = DEFAULT_STITCH_DISCOUNT
    # --- refit provenance (DESIGN.md §15) ----------------------------------
    # ``None`` = probe-only / pinned coefficients.  Otherwise the
    # fingerprint of the offline refit model (``tools/tune.py refit``)
    # that replaced them: ``fingerprint`` / ``tuning_key`` then grow a
    # ``+refit`` suffix so tuned-cache records never mix fitted and
    # probe-only machines — the same isolation rule as PR 9's ``+net``.
    refit_fingerprint: Optional[str] = None

    # ---------------------------------------------------------------------
    @property
    def network_calibrated(self) -> bool:
        """True when the interconnect probes parameterized this model."""
        return self.ici_bandwidth_gbps is not None

    @property
    def _provenance(self) -> str:
        """Provenance suffix shared by ``fingerprint`` and ``tuning_key``:
        ``+net`` for network-calibrated models, ``+refit`` for offline-
        refitted coefficients — composable (``+net+refit``)."""
        return (("+net" if self.network_calibrated else "")
                + ("+refit" if self.refit_fingerprint else ""))

    @property
    def fingerprint(self) -> str:
        """Short digest of every model constant.

        Cache keys that would otherwise trust ``name`` alone include this:
        two calibrations of the same host share a name but can carry
        different measured constants, and analytical plans derived from
        one must not be served for the other.  Network-calibrated models
        carry a ``+net`` provenance suffix and offline-refitted models a
        ``+refit`` suffix so the digest alone makes the calibration state
        legible in cache records and logs.
        """
        blob = repr(dataclasses.astuple(self)).encode()
        digest = hashlib.md5(blob).hexdigest()[:8]
        return digest + self._provenance

    @property
    def tuning_key(self) -> str:
        """Name used to key :class:`~repro.core.autotune.TuningCache`
        records.  Uncalibrated machines keep their plain ``name`` (existing
        on-disk records stay valid); network-calibrated machines get a
        ``+net`` suffix and offline-refitted machines a ``+refit`` suffix
        so their records never mix with probe-only ones — the cost models
        rank candidates differently (DESIGN.md §14/§15).
        """
        return self.name + self._provenance

    def peak(self, dtype) -> float:
        return self.peak_flops[canonical_dtype(dtype)]

    def reg_tile(self, dtype) -> tuple[int, int]:
        """Native (sublane, lane) register tile for ``dtype``.

        The analogue of the paper's SVL-determined tile: on M4 a ZA fp32
        tile is 16x16; on TPU the packing granule is (8,128) fp32 /
        (16,128) bf16 / (32,128) int8.
        """
        return (self.sublanes[canonical_dtype(dtype)], self.lanes)

    def mxu_tile(self) -> tuple[int, int]:
        return (self.mxu_rows, self.mxu_cols)

    # Roofline helpers ------------------------------------------------------
    def compute_seconds(self, flops: float, dtype="bfloat16", chips: int = 1) -> float:
        return flops / (self.peak(dtype) * chips)

    def memory_seconds(self, nbytes: float, chips: int = 1) -> float:
        return nbytes / (self.hbm_bw * chips)

    def collective_seconds(self, nbytes: float, chips: int = 1,
                           collective: str = "all_gather") -> float:
        """Seconds to move ``nbytes`` through one ``collective``.

        Calibrated path: measured all_gather bandwidth scaled by the
        per-collective efficiency ratio, plus the measured launch cost —
        the §III-style "honest" model the mesh planner charges
        (DESIGN.md §14).  Uncalibrated path: the pinned per-link
        aggregate, launch cost folded in from ``launch_overhead_s`` so
        gathered/distributed candidates still rank.
        """
        if self.network_calibrated:
            eff = 1.0
            if self.collective_efficiency:
                eff = self.collective_efficiency.get(collective, 1.0)
            bw = self.ici_bandwidth_gbps * 1e9 * max(eff, 1e-6)
            launch = self.collective_launch_s or 0.0
            return launch + nbytes / (bw * chips)
        # Aggregate ICI model: each chip drives ici_links links.
        return (self.launch_overhead_s
                + nbytes / (self.ici_bw_per_link * chips))

    # Calibration -----------------------------------------------------------
    @classmethod
    def from_probes(cls, probes: Union[Mapping[str, "object"], Iterable],
                    base: "MachineModel" = None,
                    name: str = "calibrated") -> "MachineModel":
        """Build a calibrated model from ``repro.core.microbench`` probes.

        ``probes`` is the dict returned by ``microbench.characterize`` (or
        any iterable of its ``ProbeResult``s).  Recognized probes override
        the corresponding ``base`` constants (default: ``CPU_HOST``):

          * ``matmul_<dtype>``  [GFLOP/s] -> ``peak_flops[dtype]``
          * ``copy_bw``         [GB/s]    -> ``hbm_bw``
          * ``dispatch_latency``[us]      -> ``step_overhead_s``
          * ``all_gather_bw``   [GB/s]    -> ``ici_bandwidth_gbps``
          * ``all_to_all_bw`` / ``psum_bw`` [GB/s]
                                -> ``collective_efficiency`` ratios
          * ``collective_latency`` [us]   -> ``collective_launch_s``

        Unrecognized probes (e.g. the ``target_*`` echo entries) are
        ignored; missing probes leave the base constant in place — a
        partial probe run still yields a usable model (DESIGN.md §7).
        The interconnect probes are all-or-nothing per DESIGN.md §14: on a
        1-device host they report value 0 and the network fields stay the
        explicit ``None`` ("not network-calibrated"), never a fake number.
        """
        base = base if base is not None else CPU_HOST
        if isinstance(probes, Mapping):
            probes = probes.values()
        peak = dict(base.peak_flops)
        hbm_bw = base.hbm_bw
        overhead = base.step_overhead_s
        launch = base.launch_overhead_s
        net = {}
        for p in probes:
            pname, value = p.name, p.value
            if pname.startswith("matmul_"):
                dtype = pname[len("matmul_"):]
                if dtype in peak and value > 0:
                    peak[dtype] = value * 1e9
            elif pname == "copy_bw" and value > 0:
                hbm_bw = value * 1e9
            elif pname == "dispatch_latency" and value > 0:
                # The probe measures one full dispatch round-trip: it is
                # both the per-step pipeline cost bound (PR 2 semantics)
                # and the per-pallas_call launch cost the fused GEMM path
                # amortizes (DESIGN.md §8).
                overhead = value * 1e-6
                launch = value * 1e-6
            elif pname in ("all_gather_bw", "all_to_all_bw", "psum_bw",
                           "collective_latency") and value > 0:
                net[pname] = value
        kwargs = dict(name=name, peak_flops=peak, hbm_bw=hbm_bw,
                      step_overhead_s=overhead, launch_overhead_s=launch)
        if "all_gather_bw" in net:
            ag = net["all_gather_bw"]
            eff = {"all_gather": 1.0}
            if "all_to_all_bw" in net:
                eff["all_to_all"] = net["all_to_all_bw"] / ag
            if "psum_bw" in net:
                eff["psum"] = net["psum_bw"] / ag
            kwargs["ici_bandwidth_gbps"] = ag
            kwargs["collective_efficiency"] = eff
            kwargs["collective_launch_s"] = (
                net["collective_latency"] * 1e-6
                if "collective_latency" in net else launch)
        return dataclasses.replace(base, **kwargs)


# fp8 support is build-dependent: gate every fp8 path on this flag
# instead of letting an AttributeError surface mid-dispatch (DESIGN.md
# §13).  ``FP8_DTYPE`` is the jnp dtype when present, else None.
HAS_FP8 = hasattr(jnp, "float8_e4m3fn")
FP8_DTYPE = jnp.float8_e4m3fn if HAS_FP8 else None


def canonical_dtype(dtype) -> str:
    """Canonical descriptor dtype name ("bfloat16"/"float32"/...) for any
    dtype-like — descriptors never store raw ``jnp.dtype`` objects."""
    if isinstance(dtype, str) and dtype in ("float8_e4m3", "float8_e4m3fn"):
        # The canonical name maps the *fn* jnp dtype; accept it even on
        # builds without the dtype so descriptors mentioning fp8 can be
        # keyed (execution is gated separately on HAS_FP8).
        return "float8_e4m3"
    d = jnp.dtype(dtype)
    if d == jnp.dtype(jnp.bfloat16):
        return "bfloat16"
    if d == jnp.dtype(jnp.float32):
        return "float32"
    if d == jnp.dtype(jnp.float16):
        return "float16"
    if d == jnp.dtype(jnp.int8):
        return "int8"
    if HAS_FP8 and d == jnp.dtype(FP8_DTYPE):
        return "float8_e4m3"
    if d == jnp.dtype(jnp.float64):
        return "float64"
    raise ValueError(f"unsupported dtype for machine model: {dtype}")


# TPU v5e constants, the model of JAX ``device_kind`` "TPU v5 lite".
# Peaks from the Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s.  fp32 through the MXU runs at
# half the bf16 rate with fp32 accumulate (an estimate, not a published
# figure) — mirroring the dtype asymmetry the paper measures in Table I
# (where M4 is FP32-centric; v5e is bf16-centric: the engine's dtype
# default flips accordingly).
TPU_V5E = MachineModel(
    name="tpu_v5e",
    mxu_rows=128,
    mxu_cols=128,
    peak_flops={
        "bfloat16": 197e12,
        "float16": 197e12,
        "float32": 98.5e12,
        "int8": 393e12,
        "float8_e4m3": 393e12,  # fp8 rides the int8 MAC rate
        "float64": 0.5e12,  # emulated; not a target dtype
    },
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    vmem_bytes=128 * 1024**2,
    sublanes={"float32": 8, "bfloat16": 16, "float16": 16, "int8": 32,
              "float8_e4m3": 32, "float64": 8},
    lanes=128,
    ici_bw_per_link=50e9,
    ici_links=4,
    dcn_bw=25e9 / 8,  # ~25 Gb/s effective per chip across pods
)

# The CPU host we validate on (interpret mode); no ``device_kind`` maps to
# it.  Only used to sanity-scale wall-clock expectations in benchmarks;
# never by the planner.
CPU_HOST = MachineModel(
    name="cpu_host",
    mxu_rows=1,
    mxu_cols=1,
    peak_flops={"bfloat16": 5e9, "float16": 5e9, "float32": 1e10, "int8": 2e10,
                "float8_e4m3": 2e10, "float64": 5e9},
    hbm_bytes=32 * 1024**3,
    hbm_bw=20e9,
    vmem_bytes=1 * 1024**2,
    sublanes={"float32": 8, "bfloat16": 16, "float16": 16, "int8": 32,
              "float8_e4m3": 32, "float64": 8},
    lanes=128,
    ici_bw_per_link=1e9,
    ici_links=1,
    dcn_bw=1e9,
)

DEFAULT_MACHINE = TPU_V5E

# Built-in models by the ``device_kind`` JAX reports for a TPU chip.
MACHINES_BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E}


def get_machine(name: str = "tpu_v5e") -> MachineModel:
    """Look up a built-in machine model by name."""
    return {"tpu_v5e": TPU_V5E, "cpu_host": CPU_HOST}[name]


def machine_for_device(device) -> MachineModel:
    """The pinned model of a JAX ``device``: on a TPU, looked up by its
    ``device_kind`` (a kind with no model is an error, not a default);
    off a TPU — the CPU test backend — the v5e planning target."""
    if device.platform != "tpu":
        return DEFAULT_MACHINE
    model = MACHINES_BY_DEVICE_KIND.get(device.device_kind)
    if model is None:
        raise ValueError(
            f"no machine model for TPU device_kind {device.device_kind!r}; "
            f"known: {sorted(MACHINES_BY_DEVICE_KIND)}")
    return model


def _validate_refit(data, base: MachineModel) -> Optional[str]:
    """The reason a refit-model payload cannot be applied, or None."""
    if not isinstance(data, dict):
        return "not a JSON object"
    if data.get("kind") != "machine-refit":
        return f"kind={data.get('kind')!r}, expected 'machine-refit'"
    if data.get("version") != REFIT_MODEL_VERSION:
        return (f"version={data.get('version')!r}, expected "
                f"{REFIT_MODEL_VERSION} (stale model or stale reader)")
    fp = data.get("fingerprint")
    if not isinstance(fp, str) or not fp:
        return "missing provenance fingerprint"
    if data.get("base") not in (None, base.name):
        return (f"fitted against base {data.get('base')!r}, "
                f"refusing to overlay onto {base.name!r}")
    coeffs = data.get("coefficients")
    if not isinstance(coeffs, dict) or not coeffs:
        return "missing coefficients"
    for key, value in coeffs.items():
        if key not in REFIT_COEFFICIENTS:
            return f"unknown coefficient {key!r} (stale reader?)"
        if key == "collective_efficiency":
            if not isinstance(value, dict) or not all(
                    isinstance(k, str) and isinstance(v, (int, float))
                    and math.isfinite(v) and v > 0
                    for k, v in value.items()):
                return "collective_efficiency must map names to ratios > 0"
        elif (not isinstance(value, (int, float)) or isinstance(value, bool)
              or not math.isfinite(value) or value < 0):
            return f"coefficient {key}={value!r} is not a finite number >= 0"
    return None


def load_refit_model(path: str,
                     base: Optional[MachineModel] = None) -> MachineModel:
    """Overlay an offline-refit coefficient model onto ``base``.

    Reads the versioned JSON that ``tools/tune.py refit`` emits and
    returns ``base`` with the fitted cost coefficients applied and
    ``refit_fingerprint`` set — so ``fingerprint`` / ``tuning_key`` grow
    the ``+refit`` provenance suffix (DESIGN.md §15).

    Degradation mirrors the tuning cache's: a missing, corrupt, stale
    (wrong version/kind), wrong-base or out-of-range file warns once and
    returns ``base`` unchanged — a bad refit artifact must never take
    down serving, it just keeps the probe-only model.
    """
    base = base if base is not None else DEFAULT_MACHINE
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        warnings.warn(f"ignoring refit model {path}: {e}")
        return base
    reason = _validate_refit(data, base)
    if reason is not None:
        warnings.warn(f"ignoring refit model {path}: {reason}")
        return base
    return dataclasses.replace(base, **data["coefficients"],
                               refit_fingerprint=data["fingerprint"])
