"""The MoE layer of granite-4.0-h-small at a small size (one period of
ten layers, 8 experts, top-3, a shared expert), on the CPU: dropless
routing does not depend on the batch, the shares of disjoint sets of
held experts add up to the uncut layer, and the engine counts the rows
it routes (DESIGN.md §16)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import LanguageModel
from repro.models.attention import PageSpec
from repro.models.mlp import mlp_apply
from repro.models.moe import moe_apply, moe_dropless, moe_init, moe_layer
from repro.runtime.batching import ContinuousBatchingEngine, Request


def small(**kw):
    """granite-4.0-h-small's structure and scalars at small widths."""
    base = dict(num_layers=10, d_model=64, num_heads=4, num_kv_heads=2,
                head_dim=16, d_ff=32, shared_expert_d_ff=48, num_experts=8,
                num_experts_per_tok=3, ssm_state=16, ssm_head_dim=16,
                ssm_chunk=8, vocab_size=256, dtype="float32",
                kv_cache_dtype="float32", logits_dtype="float32",
                remat=False)
    base.update(kw)
    return dataclasses.replace(get_config("granite-4.0-h-small"), **base)


def _x(shape, seed=4):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _share(params, lo, n):
    """The held experts ``lo .. lo + n - 1`` of an uncut layer's params."""
    out = dict(params)
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = {"w": params[k]["w"][lo:lo + n]}
    return out


def test_dropless_moe_is_batch_independent():
    """A token's output is the same alone and inside a batch of 5 other
    tokens (to float32 rounding: XLA's CPU matmul blocks by the row count);
    capacity dispatch at a binding capacity moves it by far more."""
    cfg = small(experts_held=4, first_expert_held=2, capacity_factor=0.25)
    params = moe_init(jax.random.PRNGKey(3), cfg)
    x = _x((1, 6, cfg.d_model))
    live = jnp.ones((1, 6), bool)
    batch, _, _ = moe_layer(params, cfg, x, live=live)
    for i in range(6):
        alone, _, _ = moe_layer(params, cfg, x[:, i:i + 1], live=live[:, :1])
        np.testing.assert_allclose(np.asarray(batch[0, i]),
                                   np.asarray(alone[0, 0]),
                                   rtol=1e-6, atol=1e-6)
    # the capacity path over all experts: alone nothing drops, in groups
    # of 64 tokens at this capacity the tokens' later picks do
    full = dataclasses.replace(cfg, experts_held=0)
    p_full = moe_init(jax.random.PRNGKey(3), full)
    xs = _x((1, 2048, cfg.d_model))
    y_batch, _ = moe_apply(p_full, full, xs)
    y_alone = jnp.concatenate([moe_apply(p_full, full, xs[:, i:i + 1])[0]
                               for i in range(8)], axis=1)
    assert float(jnp.abs(y_batch[:, :8] - y_alone).max()) > 1e-2


def test_inactive_rows_route_to_no_expert():
    cfg = small()
    params = moe_init(jax.random.PRNGKey(3), cfg)
    x = _x((5, 1, cfg.d_model))
    live = jnp.asarray([[True], [False], [True], [False], [False]])
    _, counts = moe_dropless(params, cfg, x, live)
    assert int(counts[0]) == 2 * cfg.num_experts_per_tok


@pytest.mark.parametrize("share", [1, 2, 4])
def test_held_shares_sum_to_the_uncut_layer(share):
    """The layer cut to every disjoint set of ``share`` held experts, the
    shared expert counted once, adds up to the uncut layer, and the uncut
    layer is the naive mixture over all experts (gates a softmax over the
    kept top-k logits)."""
    cfg = small()
    params = moe_init(jax.random.PRNGKey(5), cfg)
    x = _x((2, 7, cfg.d_model))
    live = jnp.ones((2, 7), bool)
    whole, _, counts = moe_layer(params, cfg, x, live=live)
    parts, rows = 0.0, 0
    for lo in range(0, cfg.num_experts, share):
        c = dataclasses.replace(cfg, experts_held=share, first_expert_held=lo)
        y, n = moe_dropless(_share(params, lo, share), c, x, live)
        parts, rows = parts + y, rows + int(n[0])
    shared = mlp_apply(params["shared"], dataclasses.replace(
        cfg, d_ff=cfg.shared_expert_d_ff), x)
    # float32 sums of the same terms in another grouping
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert rows == int(counts[0]) == 2 * 7 * cfg.num_experts_per_tok

    t = x.reshape(-1, cfg.d_model)
    top, idx = jax.lax.top_k(t @ params["router"]["w"],
                             cfg.num_experts_per_tok)
    gates = jax.nn.softmax(top, -1)
    naive = 0.0
    for e in range(cfg.num_experts):
        h = jax.nn.silu(t @ params["w_gate"]["w"][e]) * \
            (t @ params["w_up"]["w"][e])
        w_e = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        naive = naive + w_e * (h @ params["w_down"]["w"][e])
    np.testing.assert_allclose(np.asarray(parts).reshape(-1, cfg.d_model),
                               np.asarray(naive), rtol=1e-5, atol=1e-5)


def test_engine_counts_the_rows_it_routes():
    """With every expert held, each token routes exactly k rows in every
    layer: the engine's counters read prompt tokens x k x layers for the
    prefills and decoded tokens x k x layers for the decode steps."""
    cfg = small()
    params = LanguageModel.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                   spec=PageSpec(16, 8, 4))
    prompts = [9, 14, 6]
    for i, n in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=np.arange(n, dtype=np.int32) % 50,
                           max_new=4))
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    per_token = cfg.num_experts_per_tok * cfg.num_layers
    ph = eng.phase_seconds
    assert ph["expert_rows.prefill"] == sum(prompts) * per_token
    # the first token of each request comes from its prefill
    assert ph["expert_rows.decode"] == len(prompts) * 3 * per_token
    for regime in ("prefill", "decode"):
        assert 0 < ph["experts_hit." + regime] <= \
            ph["expert_rows." + regime]
