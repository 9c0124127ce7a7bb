"""Machine-characterization harness — the paper's §III in library form.

The paper microbenchmarks M4 (instruction throughput per dtype, ZA
load/store strategies, multi-core scaling) and feeds the findings into
the code generator.  This module provides the same probes for whatever
device JAX is running on, and — closing the paper's measure→generate
loop — :func:`calibrate` folds the probe results into a
:class:`~repro.core.machine.MachineModel` via
:meth:`~repro.core.machine.MachineModel.from_probes`, so every planner
cost model in ``repro.core.blocking`` ranks candidate tilings against the
*measured* host instead of pinned Table-I constants (DESIGN.md §7).
benchmarks/table1_throughput.py, fig23_bandwidth.py and fig1_scaling.py
are the reporting front-ends.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .machine import CPU_HOST, MachineModel, TPU_V5E


@dataclasses.dataclass
class ProbeResult:
    """One measured characterization probe: name, value, unit."""

    name: str
    value: float
    unit: str


def _timeit(fn, *args, iters=5, warmup=2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def probe_matmul_flops(dtype="float32", size=512, iters=5) -> ProbeResult:
    """Peak-ish matmul throughput on the host (Table I analogue).

    Covers the quant axis too (DESIGN.md §13): ``dtype="int8"`` times an
    integer contraction with an int32 accumulator — a plain ``a @ b``
    would overflow and measure nothing — and ``"float8_e4m3"`` (gated on
    :data:`~repro.core.machine.HAS_FP8`) an fp8 one with f32 accumulate,
    exactly the MACs the quantized kernels issue.
    """
    rng = np.random.default_rng(0)
    if dtype == "int8":
        a = jnp.asarray(rng.integers(-127, 128, (size, size)), jnp.int8)
        b = jnp.asarray(rng.integers(-127, 128, (size, size)), jnp.int8)
        f = jax.jit(lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32))
    elif dtype in ("float8_e4m3", "float8_e4m3fn"):
        from .machine import FP8_DTYPE, HAS_FP8
        if not HAS_FP8:
            raise ValueError("float8_e4m3 unavailable in this jax build")
        a = jnp.asarray(rng.standard_normal((size, size)), FP8_DTYPE)
        b = jnp.asarray(rng.standard_normal((size, size)), FP8_DTYPE)
        f = jax.jit(lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    else:
        a = jnp.asarray(rng.standard_normal((size, size)), dtype)
        b = jnp.asarray(rng.standard_normal((size, size)), dtype)
        f = jax.jit(lambda a, b: a @ b)
    s = _timeit(f, a, b, iters=iters)
    return ProbeResult(f"matmul_{dtype}", 2 * size**3 / s / 1e9, "GFLOP/s")


def probe_copy_bandwidth(mbytes=64) -> ProbeResult:
    """Streaming copy bandwidth (Fig 2/3 baseline analogue)."""
    n = mbytes * 2**20 // 4
    x = jnp.zeros((n,), jnp.float32)
    f = jax.jit(lambda x: x + 1.0)
    s = _timeit(f, x)
    return ProbeResult("copy_bw", 2 * n * 4 / s / 1e9, "GB/s")


def probe_elementwise_latency() -> ProbeResult:
    """Small-op dispatch latency (grid-step overhead calibration)."""
    x = jnp.zeros((8,), jnp.float32)
    f = jax.jit(lambda x: x * 2.0)
    s = _timeit(f, x, iters=20, warmup=5)
    return ProbeResult("dispatch_latency", s * 1e6, "us")


# --- interconnect probes (DESIGN.md §14) ---------------------------------
# Each measures one collective over a 1-D mesh spanning every visible
# device (a real TPU slice, or a host-count-forced CPU mesh under
# ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  Below 2
# devices there is no interconnect to measure: the probes return an
# explicit 0.0 "(uncalibrated)" result — never silently skipped — and
# ``MachineModel.from_probes`` maps that to ``None`` network fields, so
# the machine fingerprint / tuning key carry the uncalibrated provenance.

_LANES = 128


def _probe_mesh():
    devs = jax.devices()
    if len(devs) < 2:
        return None
    from jax.sharding import Mesh
    return Mesh(np.array(devs), ("probe",))


def _shmap_collective(mesh, body, out_spec):
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("probe"),
                                 out_specs=out_spec, check_vma=False))


def probe_all_gather(mbytes: int = 4, iters: int = 5) -> ProbeResult:
    """Per-device ``all_gather`` receive bandwidth over the device mesh."""
    mesh = _probe_mesh()
    if mesh is None:
        return ProbeResult("all_gather_bw", 0.0, "GB/s (uncalibrated)")
    from jax.sharding import PartitionSpec as P
    s = mesh.devices.size
    rows = max(s, mbytes * 2**20 // (4 * _LANES * s)) * s
    x = jnp.zeros((rows, _LANES), jnp.float32)
    f = _shmap_collective(
        mesh, lambda x: jax.lax.all_gather(x, "probe", tiled=True), P(None))
    t = _timeit(f, x, iters=iters)
    recv = (s - 1) * (rows // s) * _LANES * 4  # bytes received per device
    return ProbeResult("all_gather_bw", recv / t / 1e9, "GB/s")


def probe_all_to_all(mbytes: int = 4, iters: int = 5) -> ProbeResult:
    """Per-device ``all_to_all`` exchange bandwidth over the device mesh."""
    mesh = _probe_mesh()
    if mesh is None:
        return ProbeResult("all_to_all_bw", 0.0, "GB/s (uncalibrated)")
    from jax.sharding import PartitionSpec as P
    s = mesh.devices.size
    rows = max(s, mbytes * 2**20 // (4 * _LANES * s)) * s * s
    x = jnp.zeros((rows, _LANES), jnp.float32)
    f = _shmap_collective(
        mesh,
        lambda x: jax.lax.all_to_all(
            x.reshape(s, rows // s // s, _LANES), "probe",
            split_axis=0, concat_axis=0).reshape(rows // s, _LANES),
        P("probe"))
    t = _timeit(f, x, iters=iters)
    moved = (s - 1) * (rows // s // s) * _LANES * 4  # bytes sent per device
    return ProbeResult("all_to_all_bw", moved / t / 1e9, "GB/s")


def probe_psum(mbytes: int = 4, iters: int = 5) -> ProbeResult:
    """Per-device ``psum`` (all-reduce) bandwidth over the device mesh."""
    mesh = _probe_mesh()
    if mesh is None:
        return ProbeResult("psum_bw", 0.0, "GB/s (uncalibrated)")
    from jax.sharding import PartitionSpec as P
    s = mesh.devices.size
    rows = max(s, mbytes * 2**20 // (4 * _LANES * s)) * s
    x = jnp.zeros((rows, _LANES), jnp.float32)
    f = _shmap_collective(
        mesh, lambda x: jax.lax.psum(x, "probe"), P(None))
    t = _timeit(f, x, iters=iters)
    # ring all-reduce moves ~2*(s-1)/s of the per-device payload
    moved = 2 * (s - 1) * (rows // s) * _LANES * 4 / s
    return ProbeResult("psum_bw", moved / t / 1e9, "GB/s")


def probe_collective_latency(iters: int = 20) -> ProbeResult:
    """Launch latency of a tiny collective (the per-collective fixed cost
    the mesh cost model charges on top of bandwidth)."""
    mesh = _probe_mesh()
    if mesh is None:
        return ProbeResult("collective_latency", 0.0, "us (uncalibrated)")
    from jax.sharding import PartitionSpec as P
    s = mesh.devices.size
    x = jnp.zeros((8 * s,), jnp.float32)
    f = _shmap_collective(
        mesh, lambda x: jax.lax.psum(x, "probe"), P(None))
    t = _timeit(f, x, iters=iters, warmup=5)
    return ProbeResult("collective_latency", t * 1e6, "us")


def characterize(machine: MachineModel = TPU_V5E, *,
                 size: int = 512, mbytes: int = 64) -> Dict[str, ProbeResult]:
    """Run all probes; pair host measurements with target-model constants."""
    from .machine import HAS_FP8
    out = {}
    dtypes = ["float32", "bfloat16", "int8"]
    if HAS_FP8:
        dtypes.append("float8_e4m3")
    for dtype in dtypes:
        r = probe_matmul_flops(dtype, size=size)
        out[r.name] = r
        out[f"target_peak_{dtype}"] = ProbeResult(
            f"target_peak_{dtype}", machine.peak(dtype) / 1e9, "GFLOP/s")
    r = probe_copy_bandwidth(mbytes=mbytes)
    out[r.name] = r
    out["target_hbm_bw"] = ProbeResult("target_hbm_bw",
                                       machine.hbm_bw / 1e9, "GB/s")
    out[probe_elementwise_latency().name] = probe_elementwise_latency()
    # Interconnect probes (DESIGN.md §14) — always present, value 0.0
    # "(uncalibrated)" on 1-device hosts rather than silently absent.
    net_mb = min(mbytes, 4)
    for r in (probe_all_gather(mbytes=net_mb), probe_all_to_all(mbytes=net_mb),
              probe_psum(mbytes=net_mb), probe_collective_latency()):
        out[r.name] = r
    out["target_ici_bw"] = ProbeResult(
        "target_ici_bw", machine.ici_bw_per_link / 1e9, "GB/s")
    return out


def calibrate(base: Optional[MachineModel] = None, *, size: int = 512,
              mbytes: int = 64, name: str = "calibrated_host",
              refit: Optional[str] = None) -> MachineModel:
    """Probe the host and return the calibrated machine model.

    The measure→generate loop in one call: §III probes in,
    planner-parameterizing model out.  ``size``/``mbytes`` shrink the
    probe problem for fast smoke runs; ``base`` supplies the constants
    the probes don't measure (memory capacities, tile geometry).

    ``refit`` optionally overlays a fleet-fitted refit-model JSON
    (``tools/tune.py refit``, DESIGN.md §15) on the probed model: the
    probes measure this host's rooflines, the refit supplies dispatch
    coefficients regressed from real kernel timings.  A bad refit file
    warns and leaves the probed model unchanged.
    """
    probes = characterize(base if base is not None else CPU_HOST,
                          size=size, mbytes=mbytes)
    model = MachineModel.from_probes(probes, base=base, name=name)
    if refit:
        from .machine import load_refit_model
        model = load_refit_model(refit, base=model)
    return model


if __name__ == "__main__":
    for name, r in characterize().items():
        print(f"{r.name:24s} {r.value:12.2f} {r.unit}")
    m = calibrate()
    print(f"calibrated: peak_f32={m.peak('float32')/1e9:.1f} GFLOP/s "
          f"bw={m.hbm_bw/1e9:.1f} GB/s overhead={m.step_overhead_s*1e6:.2f} us")
