"""Prefill + decode must reproduce the full forward pass exactly — the
serving-correctness invariant, across every stateful block family."""
import dataclasses
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced_config
from repro.models import LanguageModel

CASES = [
    ("qwen3-0.6b", {}),                      # GQA + qk-norm + RoPE, tied
    ("starcoder2-15b", {}),                  # layernorm + bias
    ("recurrentgemma-9b", {}),               # RG-LRU + local attention
    ("mamba2-130m", {"ssm_chunk": 4, "d_model": 48, "ssm_head_dim": 8}),
    ("grok-1-314b", {"capacity_factor": 8.0}),   # MoE no-drop + softcaps
    ("phi3.5-moe-42b", {"capacity_factor": 8.0}),
    ("internvl2-1b", {}),                    # vision prefix
]


@pytest.mark.parametrize("arch,overrides", CASES)
def test_prefill_decode_matches_full(arch, overrides):
    cfg = reduced_config(get_config(arch), **overrides)
    params = LanguageModel.init(jax.random.PRNGKey(0), cfg)
    b, s = 2, 17  # deliberately not chunk-aligned
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size)
    feats = None
    n_mod = 0
    if cfg.modality == "vision":
        n_mod = cfg.num_modality_tokens
        feats = jax.random.normal(jax.random.PRNGKey(2),
                                  (b, n_mod, cfg.modality_dim))
    full, *_ = LanguageModel.apply(params, cfg, tokens,
                                     modality_feats=feats)
    cache = LanguageModel.init_cache(cfg, b, capacity=s + n_mod)
    pre, cache, *_ = LanguageModel.apply(
        params, cfg, tokens[:, :-1], positions=jnp.arange(s - 1 + n_mod),
        cache=cache, modality_feats=feats)
    dec, cache, *_ = LanguageModel.apply(
        params, cfg, tokens[:, -1:], positions=jnp.array([s - 1 + n_mod]),
        cache=cache)
    assert float(jnp.max(jnp.abs(full[:, :-1] - pre))) < 2e-4
    assert float(jnp.max(jnp.abs(full[:, -1:] - dec))) < 2e-4


def test_multi_token_decode_chain():
    """Token-by-token decode for 8 steps == teacher-forced forward."""
    cfg = reduced_config(get_config("qwen3-0.6b"))
    params = LanguageModel.init(jax.random.PRNGKey(0), cfg)
    b, s = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size)
    full, *_ = LanguageModel.apply(params, cfg, tokens)
    cache = LanguageModel.init_cache(cfg, b, capacity=s)
    prefix = 4
    _, cache, *_ = LanguageModel.apply(params, cfg, tokens[:, :prefix],
                                      positions=jnp.arange(prefix),
                                      cache=cache)
    outs = []
    for t in range(prefix, s):
        logit, cache, *_ = LanguageModel.apply(
            params, cfg, tokens[:, t:t + 1], positions=jnp.array([t]),
            cache=cache)
        outs.append(logit)
    got = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(full[:, prefix:] - got))) < 2e-4


def test_ring_buffer_window_cache():
    """Local-attention cache is a ring buffer: capacity < sequence works
    and matches full forward (window semantics)."""
    cfg = reduced_config(get_config("recurrentgemma-9b"))
    assert cfg.attn_window == 16
    params = LanguageModel.init(jax.random.PRNGKey(0), cfg)
    b, s = 1, 40  # longer than window
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size)
    full, *_ = LanguageModel.apply(params, cfg, tokens)
    cache = LanguageModel.init_cache(cfg, b, capacity=s)  # local capped at window
    _, cache, *_ = LanguageModel.apply(params, cfg, tokens[:, :-1],
                                      positions=jnp.arange(s - 1), cache=cache)
    dec, *_ = LanguageModel.apply(params, cfg, tokens[:, -1:],
                                    positions=jnp.array([s - 1]), cache=cache)
    assert float(jnp.max(jnp.abs(full[:, -1:] - dec))) < 2e-4
