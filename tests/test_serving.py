"""Continuous-batching serving oracle (DESIGN.md §12): the churning
paged-cache runtime must emit exactly the greedy tokens the static-batch
path emits, per sequence — across staggered arrivals, early finishes and
evict/re-admit cycles — and its fault paths must queue, free, and no-op
instead of crashing.  Mirrors tests/test_decode_consistency.py at the
request level."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core import engine
from repro.core.config import use
from repro.launch.serve import generate
from repro.models import LanguageModel
from repro.models.attention import PageSpec
from repro.runtime.batching import (ContinuousBatchingEngine, Request,
                                    poisson_trace)

# One arch per stateful block family.  Serving routes experts dropless,
# so a MoE token's output does not depend on the batch and per-sequence
# token identity holds for MoE archs too.
CASES = [
    ("qwen3-0.6b", {}),        # GQA + qk-norm + RoPE (paged KV)
    ("recurrentgemma-9b", {}),  # RG-LRU + local ring + paged KV
    ("mamba2-130m", {"ssm_chunk": 4, "d_model": 48, "ssm_head_dim": 8}),
    # Mamba-2 + NoPE GQA hybrid, 4 of 8 experts held, top-3, shared expert
    ("granite-4.0-h-small", {"num_layers": 10, "num_experts": 8,
                             "experts_held": 4, "num_experts_per_tok": 3}),
]


def _setup(arch, overrides):
    cfg = reduced_config(get_config(arch), **overrides)
    params = LanguageModel.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _static_tokens(cfg, params, req):
    out = generate(cfg, params, jnp.asarray(req.prompt)[None, :],
                   req.max_new)
    return np.asarray(out["tokens"][0])


def _assert_identical(cfg, params, reqs, result):
    for r in reqs:
        want = _static_tokens(cfg, params, r)
        got = result["outputs"][r.rid]
        assert np.array_equal(want, got), (
            f"rid={r.rid} diverged: static={want.tolist()} "
            f"continuous={got.tolist()}")


@pytest.mark.parametrize("arch,overrides", CASES)
def test_continuous_matches_static_staggered(arch, overrides):
    """Poisson-staggered arrivals with uneven max_new (early finishes):
    every request's greedy stream equals its solo static-path run."""
    cfg, params = _setup(arch, overrides)
    reqs = poisson_trace(num_requests=5, rate=0.5, prompt_lens=(6, 12),
                         max_new=(2, 7), vocab_size=cfg.vocab_size, seed=3)
    serving = ContinuousBatchingEngine(
        cfg, params, num_slots=3, spec=PageSpec(24, 8, 6))
    result = serving.run(reqs)
    assert result["metrics"]["requests"] == len(reqs)
    _assert_identical(cfg, params, reqs, result)


def test_evict_readmit_identical():
    """A pool too small for the full batch forces evict → requeue →
    re-prefill; greedy streams still match the uninterrupted path."""
    cfg, params = _setup(*CASES[0])
    reqs = poisson_trace(num_requests=4, rate=2.0, prompt_lens=10,
                         max_new=8, vocab_size=cfg.vocab_size, seed=1)
    serving = ContinuousBatchingEngine(
        cfg, params, num_slots=3, spec=PageSpec(9, 4, 8))
    result = serving.run(reqs)
    assert result["metrics"]["evictions"] > 0, \
        "case must exercise the eviction path"
    serving.pool.check_invariants([0] * serving.num_slots)
    _assert_identical(cfg, params, reqs, result)


def test_admission_beyond_capacity_queues():
    """More concurrent requests than slots/pages: late arrivals wait in
    the queue (head-of-line FIFO) instead of crashing, and all finish."""
    cfg, params = _setup(*CASES[0])
    reqs = [Request(rid=i,
                    prompt=np.full(8, 7 + i, np.int32),
                    max_new=4, arrival=0.0) for i in range(6)]
    serving = ContinuousBatchingEngine(
        cfg, params, num_slots=2, spec=PageSpec(6, 4, 3))
    result = serving.run(reqs)
    assert sorted(result["outputs"]) == [r.rid for r in reqs]
    assert all(len(t) == 4 for t in result["outputs"].values())
    # everything drained: all pages back on the free list
    assert serving.pool.free_pages == 6
    _assert_identical(cfg, params, reqs, result)


def test_finished_at_admission_is_noop():
    """max_new=1 finishes on the prefill argmax: the decode step must
    never run for it — zero decode launches in engine.stats()."""
    cfg, params = _setup(*CASES[0])
    req = Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32),
                  max_new=1, arrival=0.0)
    with use(backend="pallas"):
        engine.reset_stats()
        serving = ContinuousBatchingEngine(
            cfg, params, num_slots=2, spec=PageSpec(8, 4, 4))
        result = serving.run([req])
        launches = engine.stats().get("flash_decode",
                                      {}).get("launches", 0)
    assert result["metrics"]["decode_steps"] == 0
    assert launches == 0, "finished sequence must not launch decode"
    assert len(result["outputs"][0]) == 1
    assert serving.pool.free_pages == 8  # retirement freed all its pages


def test_launch_count_flat_under_churn():
    """The headline single-launch property: a churning batch (staggered
    arrivals, early finishes, slot reuse) re-enters ONE compiled decode
    step — flash_decode launch count stays at the first trace's value."""
    cfg, params = _setup(*CASES[0])
    reqs = poisson_trace(num_requests=4, rate=0.5, prompt_lens=(6, 10),
                         max_new=(3, 6), vocab_size=cfg.vocab_size, seed=0)
    with use(backend="pallas"):
        engine.reset_stats()
        serving = ContinuousBatchingEngine(
            cfg, params, num_slots=3, spec=PageSpec(24, 8, 6))
        result = serving.run(reqs)
        st = engine.stats()["flash_decode"]
        m = result["metrics"]
        assert m["decode_steps"] > 1 and m["requests"] == len(reqs)
        # one trace of the step == one counted launch, however often the
        # compiled step re-ran with a different batch composition
        n_attn = sum(1 for k in cfg.block_pattern if k == "attn")
        assert st["launches"] <= n_attn * 2, st
        assert st["launches"] == m["flash_decode_launches"]
        # same-backend oracle: static path also runs under pallas
        _assert_identical(cfg, params, reqs, result)


def test_lone_sequence_pool_exhaustion_raises():
    """When ONE sequence outgrows the whole pool there is no victim to
    evict — the runtime must fail loudly, not corrupt pages."""
    from repro.runtime.pages import OutOfPages
    cfg, params = _setup(*CASES[0])
    req = Request(rid=0, prompt=np.arange(1, 8, dtype=np.int32),
                  max_new=16, arrival=0.0)
    serving = ContinuousBatchingEngine(
        cfg, params, num_slots=2, spec=PageSpec(2, 4, 8))
    with pytest.raises(OutOfPages):
        serving.run([req])


def test_serve_step_carries_position():
    """The static-path decode step returns pos+1 so loops never rebuild
    the position scalar host-side (the serve-loop fix this PR)."""
    from repro.runtime.steps import make_serve_step
    cfg, params = _setup(*CASES[0])
    cache = LanguageModel.init_cache(cfg, 1, capacity=8)
    step = jax.jit(make_serve_step(cfg))
    tok = jnp.asarray([[3]], jnp.int32)
    logits, cache, pos = step(params, cache, tok, jnp.asarray(0, jnp.int32))
    assert int(pos) == 1
    _, _, pos = step(params, cache, tok, pos)
    assert int(pos) == 2


@pytest.mark.parametrize("arch,overrides", CASES)
def test_generate_decode_rows_repeats_each_prompt(arch, overrides):
    """decode_rows decodes every prompt's cache (KV, ring and recurrent
    state) repeated and returns the first copy of each: the same tokens
    as decoding at the prompts' own width, on a backend whose programs
    of different widths round alike."""
    cfg, params = _setup(arch, overrides)
    prompts = jnp.asarray(np.arange(16, dtype=np.int32).reshape(2, 8) % 11)
    want = generate(cfg, params, prompts, 4)["tokens"]
    got = generate(cfg, params, prompts, 4, decode_rows=6)["tokens"]
    assert got.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_generate_reports_engine_stats():
    """generate() snapshots engine.stats() into its result, mirroring
    launch.train's provenance reporting."""
    cfg, params = _setup(*CASES[0])
    prompts = jnp.asarray(np.arange(12, dtype=np.int32)[None, :] % 7)
    out = generate(cfg, params, prompts, 3)
    assert out["tokens"].shape == (1, 3)
    assert "engine_stats" in out and isinstance(out["engine_stats"], dict)
