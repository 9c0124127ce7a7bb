"""Fig 8/9 analogue: small-GEMM sweep, engine vs vendor library.

Paper: M=N in [1..512], K=512; generated SME kernels vs Accelerate BLAS,
for B-transposed ("nt", Fig 8) and B-normal ("nn" requiring transposition
handling, Fig 9).  Here: the planned Pallas engine (interpret mode — the
correctness path) and the XLA ``dot_general`` baseline (the "vendor
library"), wall-clock on CPU, plus the planner's modeled v5e time.  For
"nn"-with-strided-B we additionally compare the fused in-kernel transpose
vs the two-pass scratch-panel transpose (§IV-C).

Since the single-launch rework (DESIGN.md §8) the sweep also times the
fused lowering against the multi-launch lowering of the *same* plan and
reports per-call traced launch counts; the whole fused-vs-multi table is
written to ``BENCH_gemm_fused.json`` so the perf trajectory is tracked
across PRs.  ``run(smoke=True)`` is the CI end-to-end exercise of the
fused path (reduced sizes/iterations, same code paths).

Since the offline-refit loop (DESIGN.md §15) the sweep additionally:

  * writes every fused/multi winner into ``BENCH_tuning_cache.json`` —
    a real engine tuning cache, so CI can drive ``tools/tune.py refit``
    end-to-end on measured smoke data;
  * regresses the measured timings back onto the machine model
    (``repro.core.refit.fit_records``) and reports the analytical
    tier's fused-vs-multi **misrank count before vs after** the refit —
    the number the offline loop exists to reduce.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import GemmDescriptor, engine, plan_gemm, matmul, backend
from repro.core import refit as refit_lib
from repro.core.autotune import TuningCache
from repro.core.config import get_config as get_engine_config, \
    resolve_interpret
from repro.kernels.gemm import gemm
from repro.kernels.transpose import transpose

SIZES = [16, 64, 80, 128, 250, 512]
SMOKE_SIZES = [16, 80]
K = 512
FUSED_JSON = "BENCH_gemm_fused.json"
TUNING_JSON = "BENCH_tuning_cache.json"


def _launches(fn) -> int:
    """Traced pallas_call launches one eager call emits (engine counter)."""
    before = engine.stats().get("gemm", {}).get("launches", 0)
    jax.block_until_ready(fn())
    return engine.stats()["gemm"]["launches"] - before


def _fused_vs_multi(label, plan, a, b, layout, iters, warmup, entries,
                    measured=None):
    """Time the fused vs multi-launch lowering of one plan; record both
    the wall-clock delta and the traced launch counts (DESIGN.md §8).
    ``measured`` collects ``(plan_variant, us)`` pairs for the refit
    stanza (DESIGN.md §15)."""
    ff = jax.jit(lambda a, b: gemm(a, b, layout=layout, plan=plan,
                                   fused=True))
    fm = jax.jit(lambda a, b: gemm(a, b, layout=layout, plan=plan,
                                   fused=False))
    us_f = time_fn(ff, a, b, iters=iters, warmup=warmup)
    us_m = time_fn(fm, a, b, iters=iters, warmup=warmup)
    if measured is not None:
        measured.append((dataclasses.replace(plan, fused=True), us_f))
        measured.append((dataclasses.replace(plan, fused=False), us_m))
    lf = _launches(lambda: gemm(a, b, layout=layout, plan=plan, fused=True))
    lm = _launches(lambda: gemm(a, b, layout=layout, plan=plan, fused=False))
    d = plan.desc
    entries[label] = {
        "m": d.m, "n": d.n, "k": d.k, "layout": layout,
        "fused_us": round(us_f, 1), "multi_us": round(us_m, 1),
        "delta_us": round(us_m - us_f, 1),
        "speedup": round(us_m / us_f, 3) if us_f else None,
        "launches_fused": lf, "launches_multi": lm,
        "regions": len(plan.regions),
        # The analytical planner's lowering choice for this shape — the
        # --smoke regression gate fails entries where the planner chose
        # fused but the measurement says multi wins by > 10%.
        "chosen_fused": bool(plan.fused),
    }
    emit(f"fig89_fused/{label}", us_f,
         f"multi_launch_us={us_m:.0f};delta_us={us_m - us_f:.0f};"
         f"regions={len(plan.regions)};"
         f"launches_fused={lf};launches_multi={lm}")


def _pairs(measured):
    """(fused_plan, multi_plan, fused_us, multi_us) per benchmark shape —
    ``measured`` interleaves the two lowerings of each plan."""
    for i in range(0, len(measured) - 1, 2):
        (pf, uf), (pm, um) = measured[i], measured[i + 1]
        yield pf, pm, uf, um


def run(smoke: bool = False):
    rng = np.random.default_rng(0)
    sizes = SMOKE_SIZES if smoke else SIZES
    iters, warmup = (2, 1) if smoke else (3, 1)
    fused_entries = {}
    measured = []
    for layout in ("nt", "nn"):
        for mn in sizes:
            a = jnp.asarray(rng.standard_normal((mn, K)), jnp.float32)
            bshape = (mn, K) if layout == "nt" else (K, mn)
            b = jnp.asarray(rng.standard_normal(bshape), jnp.float32)
            flops = 2 * mn * mn * K

            fx = jax.jit(lambda a, b, l=layout: matmul(
                a, b, layout=l, backend_override="xla"))
            us_x = time_fn(fx, a, b)

            fp = jax.jit(lambda a, b, l=layout: gemm(a, b, layout=l))
            us_p = time_fn(fp, a, b, iters=iters, warmup=warmup)

            d = GemmDescriptor(m=mn, n=mn, k=K, layout=layout)
            plan = plan_gemm(d)
            model_us = plan.predicted_seconds() * 1e6
            emit(f"fig89/{layout}_{mn}", us_x,
                 f"xla_gflops={flops/us_x/1e3:.1f};"
                 f"pallas_interpret_us={us_p:.0f};"
                 f"planner_v5e_model_us={model_us:.2f}")

            # Fused single-launch vs multi-launch lowering of the same
            # plan (DESIGN.md §8): wall-clock + traced launch counts.
            _fused_vs_multi(f"{layout}_{mn}", plan, a, b, layout,
                            iters, warmup, fused_entries, measured)

    # A genuinely multi-region plan (Fig 7 geometry scaled to the MXU):
    # the fused path collapses its per-region launches to exactly one.
    mn_h = 640
    plan = plan_gemm(GemmDescriptor(m=mn_h, n=mn_h, k=K),
                     force_block=(256, 256))
    assert len(plan.regions) > 1, "hetero benchmark point must be multi-region"
    a = jnp.asarray(rng.standard_normal((mn_h, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, mn_h)), jnp.float32)
    _fused_vs_multi(f"hetero_{mn_h}", plan, a, b, "nn",
                    iters, warmup, fused_entries, measured)

    # Measured winners -> a real tuning-cache file, so the CI smoke run
    # can exercise ``tools/tune.py refit`` on genuine timing data.
    machine = get_engine_config().machine_model
    interpret = resolve_interpret(get_engine_config().interpret)
    if os.path.exists(TUNING_JSON):
        os.unlink(TUNING_JSON)  # a cache instance lazy-loads: start clean
    tcache = TuningCache(TUNING_JSON)
    for plan_f, plan_m, us_f, us_m in _pairs(measured):
        win, us = (plan_f, us_f) if us_f <= us_m else (plan_m, us_m)
        tcache.store(machine.tuning_key, win.desc, win, us,
                     interpret=interpret)
    emit("fig89_refit/cache", 0,
         f"wrote={TUNING_JSON};entries={len(measured) // 2}")

    # Refit stanza (DESIGN.md §15): fit the model on BOTH lowerings'
    # measured times per shape, then score fused-vs-multi ranking before
    # vs after.  Reported, not hard-gated — wall-clock ranking on a
    # loaded CI host is noisy; the deterministic round-trip is asserted
    # in tests/test_warmstart.py instead.
    fit = refit_lib.fit_records(measured, machine)
    refit_machine = refit_lib.apply_fit(machine, {
        **fit, "fingerprint": "fig89-local"})
    rank_pairs = [(pf, pm, uf, um) for pf, pm, uf, um in _pairs(measured)]
    bad0, considered = refit_lib.count_misranks(rank_pairs, machine)
    bad1, _ = refit_lib.count_misranks(rank_pairs, refit_machine)
    refit_entry = {
        "entries_fit": fit["entries"],
        "fitted": fit["fitted"],
        "residual_us": fit["residual_us"],
        "misranks_before": bad0,
        "misranks_after": bad1,
        "pairs_considered": considered,
    }
    emit("fig89_refit/misranks", 0,
         f"before={bad0};after={bad1};considered={considered};"
         f"residual_before_us={fit['residual_us']['before']};"
         f"residual_after_us={fit['residual_us']['after']}")

    with open(FUSED_JSON, "w") as f:
        json.dump({"k": K, "mode": "smoke" if smoke else "full",
                   "entries": fused_entries, "refit": refit_entry},
                  f, indent=1, sort_keys=True)
    emit("fig89_fused/json", 0, f"wrote={FUSED_JSON};"
         f"entries={len(fused_entries)}")

    if smoke:
        return

    # §IV-C: fused transpose vs two-pass panel transpose for strided B
    mn = 256
    a = jnp.asarray(rng.standard_normal((mn, K)), jnp.float32)
    b_nt = jnp.asarray(rng.standard_normal((mn, K)), jnp.float32)

    fused = jax.jit(lambda a, b: gemm(a, b, layout="nt"))
    two_pass = jax.jit(lambda a, b: gemm(a, transpose(b, bt=128),
                                         layout="nn"))
    us_f = time_fn(fused, a, b_nt, iters=3, warmup=1)
    us_t = time_fn(two_pass, a, b_nt, iters=3, warmup=1)
    err = float(jnp.max(jnp.abs(fused(a, b_nt) - two_pass(a, b_nt))))
    emit("fig9/fused_transpose_256", us_f, "strategy=in-kernel_contraction")
    emit("fig9/panel_transpose_256", us_t,
         f"strategy=scratch_panel;agreement_err={err:.1e}")
