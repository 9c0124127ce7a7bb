"""Fig 7 analogue: homogeneous vs heterogeneous register blocking.

The paper's 80x80 example needs 10 homogeneous 32x32 microkernels but
only 7 heterogeneous ones.  We sweep ragged output shapes at TPU
granularity and report microkernel counts, utilization, and the planner's
predicted v5e time for both strategies — the planner-level reproduction
of the paper's core optimization.

A second sweep closes the measure→generate loop (DESIGN.md §7): for a
few shapes it runs the empirical autotuner over the model-ranked
candidates and reports the measured model-plan vs autotuned-plan delta
plus each plan's provenance (``plan_source``).
"""
import functools

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.core import GemmDescriptor, autotune, engine, plan_gemm, use
from repro.core.config import resolve_interpret

SHAPES = [(640, 640), (320, 320), (896, 384), (2048, 272), (160, 1184),
          (80, 80)]
# Shapes small enough to time for real in interpret mode on the host.
MEASURED_SHAPES = [(80, 80), (320, 320)]
AUTOTUNE_BUDGET = 4
K = 512


def run():
    for m, n in SHAPES:
        d = GemmDescriptor(m=m, n=n, k=K)
        het = plan_gemm(d, heterogeneous=True)
        hom = plan_gemm(d, heterogeneous=False, force_block=(256, 256))
        emit(f"fig7/{m}x{n}", het.predicted_seconds() * 1e6,
             f"het_microkernels={het.num_microkernels};"
             f"hom_microkernels={hom.num_microkernels};"
             f"het_util={het.utilization:.3f};hom_util={hom.utilization:.3f};"
             f"hom_predicted_us={hom.predicted_seconds()*1e6:.1f}")

    # Quant axis (DESIGN.md §13): the measured host int8 probe next to the
    # model peak the planner prices narrow plans with, and the planner's
    # predicted narrow-vs-wide delta on one sweep shape (wire-byte traffic
    # + int8 MAC pricing both feed _predict_seconds).
    from repro.core.descriptor import resolve_quant
    from repro.core.machine import TPU_V5E
    from repro.core.microbench import probe_matmul_flops
    r = probe_matmul_flops("int8", size=256, iters=3)
    emit("fig7/quant_probe_int8", 2 * 256**3 / (r.value * 1e9) * 1e6,
         f"host_gops={r.value:.1f};"
         f"target_peak_int8_gops={TPU_V5E.peak('int8')/1e9:.0f}")
    d32 = GemmDescriptor(m=640, n=640, k=K)
    dq = GemmDescriptor(m=640, n=640, k=K, in_dtype="int8",
                        quant=resolve_quant("int8"))
    p32, pq = plan_gemm(d32), plan_gemm(dq)
    emit("fig7/quant_predicted_640", pq.predicted_seconds() * 1e6,
         f"wide_predicted_us={p32.predicted_seconds()*1e6:.2f};"
         f"in_bytes_int8={dq.in_bytes};in_bytes_f32={d32.in_bytes}")

    # Measured model-vs-autotuned delta through the engine's BUILD/RUN
    # stages (the three-tier policy's middle tier, run explicitly).
    from repro.kernels.gemm import gemm
    from repro.kernels.gemm.ops import execute as gemm_execute
    rng = np.random.default_rng(0)
    for m, n in MEASURED_SHAPES:
        a = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((K, n)), jnp.float32)
        d = GemmDescriptor(m=m, n=n, k=K)
        with use(backend="pallas") as cfg:
            model_plan = engine.plan_for(d)
            tuned_plan, timed = autotune.search(
                gemm_execute, d, cfg.machine_model, (a, b), {},
                interpret=resolve_interpret(cfg.interpret),
                budget=AUTOTUNE_BUDGET)
            model_us = time_fn(functools.partial(gemm, plan=model_plan), a, b)
            if tuned_plan is None:  # every candidate failed: model only
                emit(f"fig7/measured/{m}x{n}", model_us,
                     f"model_src={model_plan.plan_source};autotune=failed")
                continue
            tuned_us = time_fn(functools.partial(gemm, plan=tuned_plan), a, b)
        emit(f"fig7/measured/{m}x{n}", model_us,
             f"autotuned_us={tuned_us:.1f};"
             f"speedup={model_us / max(tuned_us, 1e-9):.3f};"
             f"model_src={model_plan.plan_source};"
             f"tuned_src={tuned_plan.plan_source};"
             f"candidates_timed={timed}")
