"""Calibration + autotuning subsystem (DESIGN.md §7).

Covers: probe-calibrated machine models, candidate enumeration, plan
record round-trips, the persistent tuning cache (including corrupt-file
degradation), and the engine's three-tier plan resolution — asserting
``plan_source`` provenance for every tier and the warm-start guarantee
(a populated cache file means zero autotune timings after a "restart").
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FlashDescriptor, GemmDescriptor,
                        GroupedGemmDescriptor, SsdChunkDescriptor,
                        TransposeDescriptor, autotune, candidate_plans,
                        engine, matmul, plan_flash, plan_gemm, plan_ssd,
                        plan_transpose, use)
from repro.core.jit_cache import GLOBAL_KERNEL_CACHE
from repro.core.machine import CPU_HOST, MachineModel, TPU_V5E
from repro.core.microbench import ProbeResult

RNG = np.random.default_rng(7)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.fixture(autouse=True)
def fresh_engine():
    engine.reset_stats()
    yield
    engine.reset_stats()


# ---------------------------------------------------------------------------
# Microbench-calibrated machine models
# ---------------------------------------------------------------------------

PROBES = {
    "matmul_float32": ProbeResult("matmul_float32", 50.0, "GFLOP/s"),
    "copy_bw": ProbeResult("copy_bw", 12.5, "GB/s"),
    "dispatch_latency": ProbeResult("dispatch_latency", 3.0, "us"),
    "target_peak_float32": ProbeResult("target_peak_float32", 98500.0,
                                       "GFLOP/s"),  # echo entry: ignored
}


def test_from_probes_overrides_measured_constants():
    m = MachineModel.from_probes(PROBES, base=CPU_HOST, name="cal")
    assert m.name == "cal"
    assert m.peak("float32") == pytest.approx(50e9)
    assert m.hbm_bw == pytest.approx(12.5e9)
    assert m.step_overhead_s == pytest.approx(3e-6)
    # unprobed constants come from the base
    assert m.vmem_bytes == CPU_HOST.vmem_bytes
    assert m.peak("bfloat16") == CPU_HOST.peak("bfloat16")


def test_from_probes_partial_and_iterable():
    m = MachineModel.from_probes([ProbeResult("copy_bw", 100.0, "GB/s")])
    assert m.hbm_bw == pytest.approx(100e9)
    assert m.step_overhead_s == CPU_HOST.step_overhead_s  # default base


def test_calibrated_overhead_feeds_cost_model():
    slow = dataclasses.replace(TPU_V5E, step_overhead_s=1e-3)
    d = GemmDescriptor(m=640, n=640, k=512)
    plan = plan_gemm(d)
    assert plan.predicted_seconds(slow) > plan.predicted_seconds(TPU_V5E)


def test_same_name_different_constants_plan_separately():
    """Two calibrations of one host share a name but not plans: the plan
    cache keys on the constants fingerprint, not the name alone."""
    m1 = MachineModel.from_probes(
        [ProbeResult("matmul_float32", 50.0, "GFLOP/s")], base=TPU_V5E)
    m2 = MachineModel.from_probes(
        [ProbeResult("matmul_float32", 500.0, "GFLOP/s")], base=TPU_V5E)
    assert m1.name == m2.name and m1.fingerprint != m2.fingerprint
    d = GemmDescriptor(m=640, n=640, k=512)
    engine.plan_for(d, machine=m1)
    engine.plan_for(d, machine=m2)
    assert engine.stats()["gemm"]["planner_calls"] == 2
    # and the identical model IS a cache hit
    engine.plan_for(d, machine=m1)
    assert engine.stats()["gemm"]["planner_calls"] == 2


def test_calibrate_smoke():
    from repro.core.microbench import calibrate
    m = calibrate(size=64, mbytes=1)
    assert m.name == "calibrated_host"
    assert m.peak("float32") > 0 and m.hbm_bw > 0
    assert m.step_overhead_s > 0


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------

def test_gemm_candidates_ranked_and_agree_with_planner():
    d = GemmDescriptor(m=300, n=500, k=128)
    cands = candidate_plans(d, top_k=6)
    assert 1 <= len(cands) <= 6
    times = [p.predicted_seconds(TPU_V5E) for p in cands]
    assert times == sorted(times)
    # The cheapest candidate is at least as good as the planner's pick:
    # the planner's fused bit is legality-gated while the calibrated cost
    # model may rank the multi-launch lowering of the same cover first
    # (see test_blocking's measured-loss-shapes regression), so the two
    # need not be the *same* plan.
    assert (cands[0].predicted_seconds(TPU_V5E)
            <= plan_gemm(d).predicted_seconds(TPU_V5E) * (1 + 1e-9))
    for p in cands:
        p.validate()  # every candidate covers C exactly once
    # knob-level dedup: fused and multi-launch lowerings of one region
    # cover are distinct candidates (DESIGN.md §8)
    knobs = [(p.regions, p.bk, p.fused) for p in cands]
    assert len(set(knobs)) == len(knobs)
    # Both lowerings are enumerated (the calibrated model ranks fused
    # behind multi-launch on this shape, so check the full search space).
    full = candidate_plans(d, top_k=256)
    assert any(p.fused for p in full) and any(not p.fused for p in full)


def test_flash_and_transpose_candidates():
    fd = FlashDescriptor(batch_heads=4, sq=256, sk=256, d=64)
    fc = candidate_plans(fd, top_k=4)
    assert fc[0].block_q == plan_flash(fd).block_q
    assert fc[0].block_k == plan_flash(fd).block_k
    td = TransposeDescriptor(rows=200, cols=300)
    tc = candidate_plans(td, top_k=3)
    assert tc[0].bt == plan_transpose(td).bt


def test_ssd_has_single_candidate():
    d = SsdChunkDescriptor(groups=4, q=64, n=32, p=64)
    cands = candidate_plans(d, top_k=8)
    assert len(cands) == 1
    assert cands[0] == plan_ssd(d)


def test_unknown_family_candidates_rejected():
    class FakeDesc:
        family = "conv"
    with pytest.raises(KeyError, match="candidate enumerator"):
        candidate_plans(FakeDesc())


# ---------------------------------------------------------------------------
# Plan <-> record round-trips
# ---------------------------------------------------------------------------

ROUNDTRIP_CASES = [
    plan_gemm(GemmDescriptor(m=300, n=500, k=128)),
    plan_flash(FlashDescriptor(batch_heads=4, sq=256, sk=128, d=64)),
    plan_transpose(TransposeDescriptor(rows=100, cols=300)),
    plan_ssd(SsdChunkDescriptor(groups=4, q=64, n=32, p=64)),
]


@pytest.mark.parametrize("plan", ROUNDTRIP_CASES,
                         ids=lambda p: p.desc.family)
def test_plan_record_roundtrip(plan):
    record = autotune.plan_to_record(plan)
    assert json.loads(json.dumps(record)) == record  # JSON-stable
    back = autotune.plan_from_record(plan.desc, record)
    assert back is not None
    assert back.plan_source == "autotuned"
    assert dataclasses.replace(back, plan_source=plan.plan_source) == plan


def test_forced_fused_mode_filters_candidates(tmp_path):
    """A config.fused override makes the executor ignore candidate fused
    bits, so search must only time (and persist) matching candidates —
    never record an untimed lowering (DESIGN.md §8)."""
    path = str(tmp_path / "tune.json")
    a, b = rand((48, 64)), rand((64, 80))
    with use(backend="pallas", autotune=True, autotune_budget=6,
             tuning_cache=path, fused="off"):
        matmul(a, b)
    entries = json.load(open(path))["entries"]
    assert entries and all(rec["fused"] is False
                           for rec in entries.values())


def test_plan_from_record_degrades_to_none():
    d = GemmDescriptor(m=64, n=64, k=64)
    assert autotune.plan_from_record(d, {"family": "transpose", "bt": 64}) \
        is None  # family mismatch
    assert autotune.plan_from_record(d, {"family": "gemm"}) is None  # knobs
    assert autotune.plan_from_record(d, {}) is None


# ---------------------------------------------------------------------------
# Tuning cache persistence
# ---------------------------------------------------------------------------

def test_tuning_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    d = GemmDescriptor(m=80, n=80, k=64)
    plan = plan_gemm(d)
    cache = autotune.TuningCache(path)
    assert len(cache) == 0
    assert cache.lookup(TPU_V5E.name, d, interpret=True) is None
    cache.store(TPU_V5E.name, d, plan, 123.4, interpret=True)
    # a fresh mirror (new process) reads the same winner back
    reread = autotune.TuningCache(path)
    record = reread.lookup(TPU_V5E.name, d, interpret=True)
    assert record is not None and record["us"] == pytest.approx(123.4)
    rebuilt = autotune.plan_from_record(d, record)
    assert rebuilt.regions == plan.regions and rebuilt.bk == plan.bk
    # keyed by machine and by execution mode: an interpret-timed winner
    # says nothing about compiled runs
    assert reread.lookup(CPU_HOST.name, d, interpret=True) is None
    assert reread.lookup(TPU_V5E.name, d, interpret=False) is None


def test_tuning_cache_corrupt_file_degrades(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text("{not json")
    with pytest.warns(UserWarning, match="corrupt tuning cache"):
        cache = autotune.TuningCache(str(path))
    assert len(cache) == 0
    # storing heals the file
    d = GemmDescriptor(m=80, n=80, k=64)
    cache.store(TPU_V5E.name, d, plan_gemm(d), 1.0, interpret=True)
    assert len(autotune.TuningCache(str(path))) == 1


def test_tuning_cache_wrong_schema_degrades(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text(json.dumps([1, 2, 3]))
    with pytest.warns(UserWarning, match="corrupt tuning cache"):
        assert len(autotune.TuningCache(str(path))) == 0


# ---------------------------------------------------------------------------
# Three-tier dispatch policy (the acceptance criterion)
# ---------------------------------------------------------------------------

def _gemm_operands(m=80, n=80, k=64):
    return rand((m, k)), rand((k, n))


def test_tier_model_default(tmp_path):
    a, b = _gemm_operands()
    with use(backend="pallas"):
        matmul(a, b)
    s = engine.stats()["gemm"]
    assert s["plan_source_model"] == 1
    assert s["plan_source_autotuned"] == 0
    assert s["plan_source_tuned_cache"] == 0
    assert s["autotune_timings"] == 0
    assert engine.plan_for(GemmDescriptor(m=80, n=80, k=64)
                           ).plan_source == "model"


def test_tier_autotune_then_tuned_cache_warm_start(tmp_path):
    path = str(tmp_path / "tune.json")
    a, b = _gemm_operands()
    ref = np.asarray(a) @ np.asarray(b)

    # --- "process 1": cold cache, autotune tier fires -------------------
    with use(backend="pallas", autotune=True, tuning_cache=path,
             autotune_budget=3):
        out = matmul(a, b)
        out2 = matmul(a, b)  # plan-cache hit: no second search
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out2), ref, rtol=1e-4, atol=1e-4)
    s = engine.stats()["gemm"]
    assert s["plan_source_autotuned"] == 1
    assert s["plan_source_tuned_cache"] == 0
    assert 0 < s["autotune_timings"] <= 3
    data = json.load(open(path))
    assert data["version"] == autotune.TUNING_CACHE_VERSION
    assert len(data["entries"]) == 1
    (record,) = data["entries"].values()
    assert record["family"] == "gemm" and record["us"] > 0

    # --- "process 2": restart (drop all in-memory state, keep the file);
    # the warm cache must satisfy the plan with ZERO autotune timings ----
    engine.reset_stats()
    with use(backend="pallas", autotune=True, tuning_cache=path,
             autotune_budget=3):
        out3 = matmul(a, b)
    np.testing.assert_allclose(np.asarray(out3), ref, rtol=1e-4, atol=1e-4)
    s = engine.stats()["gemm"]
    assert s["plan_source_tuned_cache"] == 1
    assert s["plan_source_autotuned"] == 0
    assert s["autotune_timings"] == 0, \
        "a populated tuning cache must not re-time candidates"


def test_tier_order_tuned_cache_preempts_autotune(tmp_path):
    """A cache entry stored out-of-band wins over a fresh search."""
    path = str(tmp_path / "tune.json")
    d = GemmDescriptor(m=80, n=80, k=64)
    pinned = plan_gemm(d, force_block=(8, 128), heterogeneous=False)
    autotune.TuningCache(path).store(TPU_V5E.name, d, pinned, 1.0,
                                     interpret=True)
    engine.reset_stats()
    a, b = _gemm_operands()
    with use(backend="pallas", autotune=True, tuning_cache=path):
        matmul(a, b)
    s = engine.stats()["gemm"]
    assert s["plan_source_tuned_cache"] == 1 and s["autotune_timings"] == 0
    with use(backend="pallas", autotune=True, tuning_cache=path):
        plan = engine.plan_for(d)
    assert plan.plan_source == "autotuned"
    assert plan.regions == pinned.regions


def test_corrupt_cache_falls_back_to_model(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text("][ definitely not json")
    a, b = _gemm_operands()
    with pytest.warns(UserWarning, match="corrupt tuning cache"):
        with use(backend="pallas", tuning_cache=str(path)):
            out = matmul(a, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)
    s = engine.stats()["gemm"]
    assert s["plan_source_model"] == 1 and s["plan_source_tuned_cache"] == 0


def test_autotuned_winner_overwrites_stale_traced_plan(tmp_path):
    """A jit trace that resolves before the tuning cache is populated
    caches a model plan on the tuned-tier key; a later eager autotune
    must overwrite it, not serve it for the rest of the process."""
    path = str(tmp_path / "tune.json")
    a, b = _gemm_operands()
    d = GemmDescriptor(m=80, n=80, k=64)
    with use(backend="pallas", autotune=True, tuning_cache=path,
             autotune_budget=3):
        jax.jit(matmul)(a, b)  # tracers: tuned tier misses, model plan cached
        assert engine.plan_for(d).plan_source == "model"
        matmul(a, b)           # concrete: autotunes + propagates the winner
        assert engine.plan_for(d).plan_source == "autotuned"


def test_env_budget_malformed_falls_back(monkeypatch):
    """A bad REPRO_AUTOTUNE_BUDGET must not take down `import repro`."""
    from repro.core import config
    monkeypatch.setenv("REPRO_AUTOTUNE_BUDGET", "abc")
    with pytest.warns(UserWarning, match="REPRO_AUTOTUNE_BUDGET"):
        assert config._env_default().autotune_budget == 8
    monkeypatch.setenv("REPRO_AUTOTUNE_BUDGET", "0")
    with pytest.warns(UserWarning, match="REPRO_AUTOTUNE_BUDGET"):
        assert config._env_default().autotune_budget == 8
    monkeypatch.setenv("REPRO_AUTOTUNE_BUDGET", "5")
    assert config._env_default().autotune_budget == 5


def test_search_short_circuits_single_candidate():
    """One candidate (ssd_chunk has no free knobs) means nothing to
    choose: no executions are timed and the model tier serves the plan."""
    d = SsdChunkDescriptor(groups=2, q=32, n=16, p=32)
    executed = []
    plan, timed = autotune.search(
        lambda *a, **k: executed.append(1), d, TPU_V5E, (), {},
        interpret=True, budget=8)
    assert plan is None and timed == 0 and not executed


def test_autotune_skipped_under_jit_tracing(tmp_path):
    """Tracers can't be timed: inside jit the policy resolves via the
    analytical model and performs zero timings."""
    path = str(tmp_path / "tune.json")
    # A shape no other test jits: jax caches traces by (function, avals),
    # and a cache hit would skip dispatch entirely.
    a, b = _gemm_operands(m=56, n=88, k=48)
    with use(backend="pallas", autotune=True, tuning_cache=path):
        out = jax.jit(matmul)(a, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)
    s = engine.stats()["gemm"]
    assert s["plan_source_model"] == 1 and s["autotune_timings"] == 0


def test_autotune_other_families(tmp_path):
    """The policy is family-agnostic: transpose autotunes and warm-starts
    through the same cache file as gemm."""
    path = str(tmp_path / "tune.json")
    from repro.kernels.transpose import transpose
    x = rand((72, 136))
    with use(backend="pallas", autotune=True, tuning_cache=path,
             autotune_budget=2):
        out = transpose(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).T)
    s = engine.stats()["transpose"]
    assert s["plan_source_autotuned"] == 1 and s["autotune_timings"] > 0
    engine.reset_stats()
    with use(backend="pallas", autotune=True, tuning_cache=path):
        transpose(x)
    s = engine.stats()["transpose"]
    assert s["plan_source_tuned_cache"] == 1 and s["autotune_timings"] == 0


# ---------------------------------------------------------------------------
# Per-phase stats reset (benchmarks/run.py contract)
# ---------------------------------------------------------------------------

def test_reset_stats_keeps_entries_for_phase_boundaries():
    a, b = _gemm_operands()
    with use(backend="pallas"):
        matmul(a, b)
    kernels_built = len(GLOBAL_KERNEL_CACHE)
    assert kernels_built > 0
    engine.reset_stats(entries=False)
    s = engine.stats()
    assert all(v == 0 for fam in s.values() for v in fam.values())
    # next "phase" reuses the warm caches: hits, no rebuilds
    with use(backend="pallas"):
        matmul(a, b)
    s = engine.stats()["gemm"]
    assert s["plan_hits"] == 1 and s["plan_misses"] == 0
    assert s["kernel_misses"] == 0 and s["kernel_hits"] >= 1
    assert len(GLOBAL_KERNEL_CACHE) == kernels_built


@pytest.mark.parametrize("interpret", [True, False])
def test_search_skips_failed_candidate_only_under_interpreter(interpret):
    """Under the interpreter a candidate whose build raises is skipped
    with a warning; compiled, the failure raises — on a chip it is a
    kernel the compiler refused, which must not lose quietly to a plan
    that still builds."""
    d = GemmDescriptor(m=64, n=64, k=64)

    def refused(*args, **kw):
        raise RuntimeError("kernel refused")

    operands = (rand((64, 64)), rand((64, 64)))
    if interpret:
        with pytest.warns(UserWarning, match="candidate failed"):
            plan, timed = autotune.search(refused, d, TPU_V5E, operands, {},
                                          interpret=True, budget=4)
        assert plan is None and timed == 0
    else:
        with pytest.raises(RuntimeError, match="kernel refused"):
            autotune.search(refused, d, TPU_V5E, operands, {},
                            interpret=False, budget=4)
