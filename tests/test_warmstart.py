"""Fleet refit + AOT warm-start (DESIGN.md §15).

Covers the offline measure→model loop and the zero-stall startup path:

  * coefficient refit — ``repro.core.refit`` recovers known machine
    coefficients exactly from synthetic measured timings, degrades on
    thin data, and ``tools/tune.py refit`` drives the same fit from raw
    cache files end-to-end;
  * refit-model provenance — the ``+refit`` tuning-key/fingerprint
    suffix, and ``load_refit_model``'s refuse-and-warn degradation for
    corrupt / stale / wrong-base artifacts;
  * descriptor manifests — ``seen_descriptors`` → ``save_manifest`` →
    ``load_manifest`` round-trips, with per-entry skip degradation;
  * the warm-start proof — autotune once, "restart", ``engine.warmup``
    over the recorded manifest, and show the serving phase dispatches
    with zero autotune timings, zero plan-cache misses and a 1.0 kernel
    hit rate — including one real continuous-batching serving step.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GemmDescriptor, TransposeDescriptor, engine,
                        matmul, plan_gemm, use)
from repro.core import refit as refit_lib
from repro.core import warmstart
from repro.core.autotune import TuningCache
from repro.core.descriptor import (FlashDecodeDescriptor, MeshSpec,
                                   QuantSpec, descriptor_from_cache_key)
from repro.core.machine import (REFIT_MODEL_VERSION, TPU_V5E,
                                load_refit_model)

RNG = np.random.default_rng(11)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.fixture(autouse=True)
def fresh_engine():
    engine.reset_stats()
    yield
    engine.reset_stats()


# ---------------------------------------------------------------------------
# Coefficient refit: exact round-trip on synthetic timings
# ---------------------------------------------------------------------------

TRUE = dataclasses.replace(
    TPU_V5E, step_overhead_s=5e-7, launch_overhead_s=4e-6,
    extra_launch_factor=0.5, fused_tile_decode_s=1e-6, stitch_discount=0.4)

SHAPES = [(128, 128, 512), (256, 512, 512), (640, 640, 512),
          (512, 1024, 1024), (80, 80, 512), (1024, 256, 2048),
          (250, 250, 512), (640, 1280, 512), (896, 384, 1024)]


def _synthetic_records(machine):
    """(plan, us) pairs whose timings come from ``machine``'s own cost
    model — block-size and fused/multi diversity makes every coefficient
    identifiable."""
    records = []
    for m, n, k in SHAPES:
        d = GemmDescriptor(m=m, n=n, k=k)
        for fb in (None, (256, 256), (128, 128), (512, 512)):
            try:
                p = plan_gemm(d, force_block=fb)
            except ValueError:
                continue
            for fused in (True, False):
                pp = dataclasses.replace(p, fused=fused)
                records.append((pp, pp.predicted_seconds(machine) * 1e6))
    return records


def test_refit_recovers_known_coefficients():
    records = _synthetic_records(TRUE)
    fit = refit_lib.fit_records(records, TPU_V5E)
    for name in ("step_overhead_s", "launch_overhead_s",
                 "extra_launch_factor", "fused_tile_decode_s",
                 "stitch_discount"):
        assert name in fit["fitted"], fit["fitted"]
        got, want = fit["coefficients"][name], getattr(TRUE, name)
        assert got == pytest.approx(want, rel=0.05), (name, got, want)
    # and the fit explains the data it was given
    assert fit["residual_us"]["after"] < fit["residual_us"]["before"]
    assert fit["residual_us"]["after"] < 1.0


def test_refit_reduces_misranks_vs_base_model():
    """The acceptance loop in miniature: the probe-only base misranks
    fused-vs-multi on timings generated by a different machine; the
    refit model ranks every measured pair correctly."""
    records = _synthetic_records(TRUE)
    pairs = [(records[i][0], records[i + 1][0],
              records[i][1], records[i + 1][1])
             for i in range(0, len(records), 2)]
    fit = refit_lib.fit_records(records, TPU_V5E)
    after_machine = refit_lib.apply_fit(
        TPU_V5E, {**fit, "fingerprint": "t"})
    bad_before, considered = refit_lib.count_misranks(pairs, TPU_V5E)
    bad_after, _ = refit_lib.count_misranks(pairs, after_machine)
    assert considered > 0
    assert bad_after == 0
    assert bad_after <= bad_before


def test_refit_unfitted_coefficients_keep_base_values():
    """Single-region fused-only records exercise neither stitch nor the
    extra-launch term: those coefficients must survive from the base,
    and ``fitted`` must not claim them."""
    d = GemmDescriptor(m=128, n=128, k=512)
    p = plan_gemm(d)
    assert len(p.regions) == 1
    pp = dataclasses.replace(p, fused=True)
    records = [(pp, pp.predicted_seconds(TRUE) * 1e6)]
    fit = refit_lib.fit_records(records, TPU_V5E)
    assert "stitch_discount" not in fit["fitted"]
    assert fit["coefficients"]["stitch_discount"] == TPU_V5E.stitch_discount
    assert fit["coefficients"]["extra_launch_factor"] == \
        TPU_V5E.extra_launch_factor


def test_refit_robust_to_outliers():
    """Huber IRLS: a handful of wildly corrupted timings must not drag
    the recovered coefficients away."""
    records = _synthetic_records(TRUE)
    corrupted = list(records)
    for i in (0, 7, 20):
        plan, us = corrupted[i]
        corrupted[i] = (plan, us * 50.0)
    fit = refit_lib.fit_records(corrupted, TPU_V5E)
    assert fit["coefficients"]["step_overhead_s"] == pytest.approx(
        TRUE.step_overhead_s, rel=0.15)
    assert fit["coefficients"]["launch_overhead_s"] == pytest.approx(
        TRUE.launch_overhead_s, rel=0.15)


def test_refit_rejects_empty():
    with pytest.raises(ValueError):
        refit_lib.fit_records([], TPU_V5E)


# ---------------------------------------------------------------------------
# Cache-entry parsing and the full fit_cache_entries payload
# ---------------------------------------------------------------------------

def _cache_with_synthetic_entries(path, machine):
    cache = TuningCache(path)
    for plan, us in _synthetic_records(machine):
        cache.store(machine.tuning_key, plan.desc, plan, us,
                    interpret=True)
    return cache


def test_fit_cache_entries_payload(tmp_path):
    """End-to-end from raw cache entries: keys parse back to plans, the
    payload is versioned + fingerprinted, and load_refit_model applies
    it with the +refit provenance suffix."""
    path = str(tmp_path / "cache.json")
    _cache_with_synthetic_entries(path, TRUE)
    entries = json.load(open(path))["entries"]
    model = refit_lib.fit_cache_entries(entries, TPU_V5E)
    assert model["version"] == REFIT_MODEL_VERSION
    assert model["kind"] == "machine-refit"
    assert model["base"] == TPU_V5E.name
    assert model["fingerprint"]
    assert model["skipped"] == 0
    # the cache holds one winner per (shape, block) key — coarser than
    # the raw record list, but still coefficient-identifying
    assert model["entries"] > 0
    assert model["coefficients"]["step_overhead_s"] == pytest.approx(
        TRUE.step_overhead_s, rel=0.05)

    out = str(tmp_path / "model.json")
    refit_lib.save_refit_model(out, model)
    fitted = load_refit_model(out, base=TPU_V5E)
    assert fitted.refit_fingerprint == model["fingerprint"]
    assert fitted.tuning_key == TPU_V5E.name + "+refit"
    assert fitted.fingerprint.endswith("+refit")
    assert fitted.step_overhead_s == pytest.approx(TRUE.step_overhead_s,
                                                   rel=0.05)
    # provenance separation: tuned records from the refit machine can
    # never serve (or be served by) the probe-only machine
    assert fitted.tuning_key != TPU_V5E.tuning_key


def test_parse_entry_degrades_to_none():
    assert refit_lib.parse_entry("garbage-key", {"us": 1.0}) is None
    assert refit_lib.parse_entry(
        "tpu_v5e|interpret|('gemm', 'not-a-valid-tuple'", {"us": 1.0}) \
        is None
    d = GemmDescriptor(m=64, n=64, k=64)
    key = f"tpu_v5e|interpret|{d.cache_key()!r}"
    assert refit_lib.parse_entry(key, {"family": "gemm"}) is None  # no us
    assert refit_lib.parse_entry(key, {"us": 1.0}) is None  # no knobs


def test_tune_cli_refit_roundtrip(tmp_path):
    """``tools/tune.py refit`` on a real cache file recovers the machine
    that generated the timings."""
    cache_path = str(tmp_path / "cache.json")
    _cache_with_synthetic_entries(cache_path, TRUE)
    spec = importlib.util.spec_from_file_location(
        "tune_cli", os.path.join(os.path.dirname(__file__), os.pardir,
                                 "tools", "tune.py"))
    tune = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tune)
    out = str(tmp_path / "model.json")
    rc = tune.main(["refit", cache_path, "--out", out,
                    "--base", "tpu_v5e"])
    assert rc == 0
    fitted = load_refit_model(out, base=TPU_V5E)
    assert fitted.refit_fingerprint
    assert fitted.step_overhead_s == pytest.approx(TRUE.step_overhead_s,
                                                   rel=0.05)
    # empty/absent input fails loudly (CLI contract), not silently
    missing = str(tmp_path / "nope.json")
    with pytest.raises(FileNotFoundError):
        tune.main(["refit", missing, "--out", out])


# ---------------------------------------------------------------------------
# load_refit_model degradation
# ---------------------------------------------------------------------------

def _good_model(tmp_path, **overrides):
    model = {"version": REFIT_MODEL_VERSION, "kind": "machine-refit",
             "base": TPU_V5E.name, "fingerprint": "abc123",
             "coefficients": {"step_overhead_s": 1e-6}}
    model.update(overrides)
    path = str(tmp_path / "m.json")
    with open(path, "w") as f:
        json.dump(model, f)
    return path


@pytest.mark.parametrize("mutation", [
    {"version": 99},                                   # stale version
    {"kind": "something-else"},                        # wrong kind
    {"base": "other_machine"},                         # wrong base model
    {"fingerprint": ""},                               # no provenance
    {"coefficients": {}},                              # nothing to apply
    {"coefficients": {"not_a_coeff": 1.0}},            # unknown key
    {"coefficients": {"step_overhead_s": -1.0}},       # negative charge
    {"coefficients": {"step_overhead_s": float("nan")}},  # non-finite
    {"coefficients": {"collective_efficiency": {"all_to_all": -2.0}}},
])
def test_load_refit_model_rejects_bad_payloads(tmp_path, mutation):
    path = _good_model(tmp_path, **mutation)
    with pytest.warns(UserWarning):
        m = load_refit_model(path, base=TPU_V5E)
    assert m == TPU_V5E  # unchanged, no +refit stamp
    assert m.refit_fingerprint is None


def test_load_refit_model_missing_and_corrupt(tmp_path):
    with pytest.warns(UserWarning):
        assert load_refit_model(str(tmp_path / "nope.json"),
                                base=TPU_V5E) == TPU_V5E
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("{this is not json")
    with pytest.warns(UserWarning):
        assert load_refit_model(bad, base=TPU_V5E) == TPU_V5E


def test_load_refit_model_applies_good_payload(tmp_path):
    path = _good_model(tmp_path)
    m = load_refit_model(path, base=TPU_V5E)
    assert m.step_overhead_s == 1e-6
    assert m.refit_fingerprint == "abc123"
    assert m.tuning_key == "tpu_v5e+refit"


# ---------------------------------------------------------------------------
# Descriptor cache-key inversion + manifests
# ---------------------------------------------------------------------------

def test_descriptor_cache_key_roundtrip():
    descs = [
        GemmDescriptor(m=64, n=128, k=256, layout="nt", epilogue="silu",
                       quant=QuantSpec(dtype="int8")),
        GemmDescriptor(m=64, n=128, k=256,
                       mesh=MeshSpec(axis="model", size=4)),
        TransposeDescriptor(rows=128, cols=64, batch=2),
        FlashDecodeDescriptor(num_seqs=4, pages=64, page_size=16,
                              max_blocks=8, num_heads=4, num_kv_heads=2,
                              head_dim=16),
    ]
    for d in descs:
        back = descriptor_from_cache_key(d.cache_key())
        assert back == d
        assert back.cache_key() == d.cache_key()


def test_descriptor_cache_key_rejects_unknown():
    with pytest.raises(ValueError):
        descriptor_from_cache_key(("no_such_family", 1, 2))
    with pytest.raises(ValueError):
        descriptor_from_cache_key(("gemm", 1, 2))  # field count mismatch


def test_manifest_roundtrip(tmp_path):
    path = str(tmp_path / "manifest.json")
    descs = [GemmDescriptor(m=32, n=32, k=64),
             TransposeDescriptor(rows=64, cols=32)]
    n = warmstart.save_manifest(path, descs + descs)  # dedup
    assert n == 2
    back = warmstart.load_manifest(path)
    assert sorted(repr(d.cache_key()) for d in back) == \
        sorted(repr(d.cache_key()) for d in descs)


def test_manifest_degradation(tmp_path):
    missing = str(tmp_path / "nope.json")
    with pytest.warns(UserWarning):
        assert warmstart.load_manifest(missing) == []
    stale = str(tmp_path / "stale.json")
    with open(stale, "w") as f:
        json.dump({"version": 999, "descriptors": []}, f)
    with pytest.warns(UserWarning):
        assert warmstart.load_manifest(stale) == []
    # one bad entry is skipped, the rest survive
    mixed = str(tmp_path / "mixed.json")
    good = repr(GemmDescriptor(m=32, n=32, k=64).cache_key())
    with open(mixed, "w") as f:
        json.dump({"version": warmstart.MANIFEST_VERSION,
                   "descriptors": [good, "('bogus_family', 1)"]}, f)
    with pytest.warns(UserWarning):
        back = warmstart.load_manifest(mixed)
    assert len(back) == 1 and back[0].family == "gemm"


# ---------------------------------------------------------------------------
# The warm-start proof: zero autotune, zero plan misses, 1.0 kernel hits
# ---------------------------------------------------------------------------

def _all_counts(stats, prefix):
    return sum(v for b in stats.values() for k, v in b.items()
               if k.startswith(prefix))


def test_engine_warmup_zero_stall_serving(tmp_path):
    """Autotune once ("fleet worker"), restart, warm up over the
    recorded manifest + cache, then dispatch the serving shapes: zero
    autotune timings, zero plan-cache misses, every kernel a hit."""
    cache = str(tmp_path / "tune.json")
    manifest = str(tmp_path / "manifest.json")
    a, b = rand((56, 72)), rand((72, 88))
    x = rand((88, 120))
    from repro.kernels.transpose import transpose

    # --- "fleet worker": autotune populates the cache ------------------
    with use(backend="pallas", autotune=True, tuning_cache=cache,
             autotune_budget=3):
        out_a = matmul(a, b)
        out_t = transpose(x)
    assert engine.save_manifest(manifest) == 2

    # --- "restart": drop every in-memory cache -------------------------
    engine.reset_stats()

    # --- serving process: warm up, then serve --------------------------
    with use(backend="pallas", autotune=False,
             tuning_cache_preload=cache):
        counts = engine.warmup(manifest=manifest)
        assert counts == {"gemm": 1, "transpose": 1}
        s = engine.stats()
        # plans came from the tuned tier for every dispatched family,
        # and the warmup counters record the work
        assert s["gemm"]["plan_source_tuned_cache"] == 1
        assert s["transpose"]["plan_source_tuned_cache"] == 1
        assert s["gemm"]["warmups"] == 1
        assert s["transpose"]["warmups"] == 1
        assert _all_counts(s, "autotune_timings") == 0

        engine.reset_stats(entries=False)  # serving stats stand alone
        out_a2 = matmul(a, b)
        out_t2 = transpose(x)
        s = engine.stats()

    assert _all_counts(s, "autotune_timings") == 0
    assert _all_counts(s, "plan_misses") == 0
    assert _all_counts(s, "kernel_misses") == 0
    kernel_hits = _all_counts(s, "kernel_hits")
    assert kernel_hits > 0  # hit rate 1.0: hits > 0, misses == 0
    assert s["gemm"]["plan_hits"] >= 1
    assert s["transpose"]["plan_hits"] >= 1
    np.testing.assert_allclose(np.asarray(out_a2), np.asarray(out_a),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_t2), np.asarray(out_t))


def test_warmup_mesh_descriptor_is_plan_only():
    """Mesh descriptors need a live device mesh: warmup must warm the
    plan tier and count the descriptor without trying to execute."""
    d = GemmDescriptor(m=64, n=128, k=64,
                       mesh=MeshSpec(axis="model", size=4))
    assert warmstart.synth_operands(d) is None
    counts = engine.warmup([d])
    assert counts == {"gemm": 1}
    assert engine.stats()["gemm"]["warmups"] == 1
    assert engine.stats()["gemm"]["launches"] == 0


def test_warmup_requires_a_population():
    with use(warm_start=None):
        with pytest.raises(ValueError):
            engine.warmup()


def test_warmup_build_failure_degrades(monkeypatch):
    """A descriptor whose build blows up still warms its plan — partial
    degradation, never a crash."""
    d = GemmDescriptor(m=32, n=64, k=64)

    def boom(*a, **kw):
        raise RuntimeError("synthetic build failure")

    monkeypatch.setattr(warmstart, "synth_operands", boom)
    with pytest.warns(UserWarning, match="warmup build failed"):
        counts = engine.warmup([d])
    assert counts == {"gemm": 1}
    assert engine.stats()["gemm"]["warmups"] == 1


def test_config_warm_start_plumbs_through(tmp_path):
    manifest = str(tmp_path / "m.json")
    warmstart.save_manifest(manifest,
                            [GemmDescriptor(m=32, n=32, k=64)])
    with use(backend="pallas", warm_start=manifest):
        counts = engine.warmup()
    assert counts == {"gemm": 1}


def test_continuous_serving_warm_step(tmp_path):
    """One real continuous-batching serving run after warmup: the
    serving phase performs zero autotune timings and zero plan-cache
    misses (DESIGN.md §15 acceptance)."""
    from repro.configs import get_config as get_arch
    from repro.configs import reduced_config
    from repro.models import LanguageModel
    from repro.models.attention import PageSpec
    from repro.runtime.batching import (ContinuousBatchingEngine,
                                        poisson_trace)

    cfg = reduced_config(get_arch("qwen3-0.6b"))
    params = LanguageModel.init(jax.random.PRNGKey(0), cfg)
    reqs = poisson_trace(num_requests=2, rate=1.0, prompt_lens=6,
                         max_new=3, vocab_size=cfg.vocab_size, seed=5)
    with use(backend="pallas"):
        # cold run records the descriptor population
        serving = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                           spec=PageSpec(24, 8, 6))
        cold = serving.run(reqs)
        manifest = str(tmp_path / "manifest.json")
        assert engine.save_manifest(manifest) > 0

        engine.reset_stats()  # "restart"
        serving = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                           spec=PageSpec(24, 8, 6))
        w = serving.warmup(prompt_lens={len(r.prompt) for r in reqs},
                           manifest=manifest)
        assert sum(w["kernels"].values()) > 0
        engine.reset_stats(entries=False)
        warm = serving.run(reqs)
        s = engine.stats()

    assert _all_counts(s, "autotune_timings") == 0
    assert _all_counts(s, "plan_misses") == 0
    for rid, toks in cold["outputs"].items():
        np.testing.assert_array_equal(warm["outputs"][rid], toks)
    ph = warm["metrics"]["phase_seconds"]
    assert set(ph) == {"admission", "prefill", "grow", "eviction", "tables",
                       "decode", "decode.dispatch", "decode.emit",
                       "queue_wait", "expert_rows.decode",
                       "experts_hit.decode", "expert_rows.prefill",
                       "experts_hit.prefill", "decode_tiles.live",
                       "decode_tiles.walked"}
    assert all(v >= 0 for v in ph.values())
