"""Layer-level correctness: norms, rope, MoE invariants, RG-LRU and SSD
against naive step-by-step recurrence oracles."""
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import common
from repro.models.rotary import apply_rope, rope_freqs
from repro.models.moe import moe_apply, moe_init
from repro.models.rglru import rglru_apply, rglru_init, _rglru_scan
from repro.models.ssd import ssd_apply, ssd_init, _ssd_chunked

RNG = np.random.default_rng(3)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def test_rmsnorm_matches_manual():
    x = rand((2, 5, 16))
    p = common.rmsnorm_init(16)
    got = common.rmsnorm(p, x, eps=1e-6)
    ref = x / np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_layernorm_zero_mean_unit_var():
    x = rand((4, 32))
    p = common.layernorm_init(32)
    y = np.asarray(common.layernorm(p, x, eps=1e-6))
    np.testing.assert_allclose(y.mean(-1), 0, atol=1e-5)
    np.testing.assert_allclose(y.var(-1), 1, atol=1e-3)


def test_rope_preserves_norm_and_relativity():
    hd = 32
    x = rand((1, 6, 2, hd))
    pos = jnp.arange(6)
    y = apply_rope(x, pos[None, :], theta=10000.0)
    # rotation preserves per-pair norms
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-4)
    # relative property: <R(p)q, R(k)k'> depends only on p-k
    q, k = rand((1, 1, 1, hd)), rand((1, 1, 1, hd))
    def dot_at(pq, pk):
        rq = apply_rope(q, jnp.array([[pq]]), 10000.0)
        rk = apply_rope(k, jnp.array([[pk]]), 10000.0)
        return float(jnp.sum(rq * rk))
    assert abs(dot_at(5, 3) - dot_at(9, 7)) < 1e-3


def test_rope_freqs_are_a_host_constant():
    """The inverse frequencies enter every program as the same literal:
    no power is traced, so no compiler can evaluate it two ways."""
    jaxpr = jax.make_jaxpr(lambda: rope_freqs(128, 1e6))()
    assert not any(e.primitive.name == "pow" for e in jaxpr.eqns)
    want = (1.0 / 1e6 ** (np.arange(64) / 64)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(rope_freqs(128, 1e6)), want)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def moe_cfg(**kw):
    return reduced_config(get_config("phi3.5-moe-42b"), **kw)


def test_moe_no_drop_equals_dense_mixture():
    """With capacity so large nothing drops, output == sum of gated expert
    FFNs computed naively."""
    cfg = moe_cfg(capacity_factor=16.0)
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = rand((2, 8, cfg.d_model))
    y, aux = moe_apply(params, cfg, x)

    # naive dense reference
    t = x.reshape(-1, cfg.d_model)
    logits = t @ params["router"]["w"]
    probs = jax.nn.softmax(logits, -1)
    vals, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    vals = vals / vals.sum(-1, keepdims=True)
    ref = jnp.zeros_like(t)
    for e in range(cfg.num_experts):
        up = t @ params["w_up"]["w"][e]
        gate = jax.nn.silu(t @ params["w_gate"]["w"][e])
        out_e = (gate * up) @ params["w_down"]["w"][e]
        w_e = jnp.sum(jnp.where(idx == e, vals, 0.0), -1, keepdims=True)
        ref = ref + w_e * out_e
    np.testing.assert_allclose(y.reshape(-1, cfg.d_model), ref,
                               atol=2e-3, rtol=2e-3)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens():
    # enough tokens that the per-group capacity (floored at 8) binds
    cfg = moe_cfg(capacity_factor=0.25)
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = rand((2, 512, cfg.d_model))
    y, _ = moe_apply(params, cfg, x)
    assert np.isfinite(np.asarray(y)).all()
    # dropped tokens produce strictly zero output rows somewhere
    norms = jnp.linalg.norm(y.reshape(-1, cfg.d_model), axis=-1)
    assert float(jnp.min(norms)) == 0.0


def test_moe_aux_loss_uniform_router_is_one():
    """With perfectly uniform routing probabilities the GShard aux loss
    equals 1 (E * E * (1/E) * (1/E))."""
    cfg = moe_cfg()
    params = moe_init(jax.random.PRNGKey(0), cfg)
    params["router"]["w"] = jnp.zeros_like(params["router"]["w"])
    x = rand((2, 32, cfg.d_model))
    _, aux = moe_apply(params, cfg, x)
    assert abs(float(aux) - 1.0) < 0.05


# ---------------------------------------------------------------------------
# RG-LRU vs naive loop
# ---------------------------------------------------------------------------

def test_rglru_scan_matches_loop():
    b, s, w = 2, 11, 8
    a = jnp.asarray(RNG.uniform(0.5, 0.99, (b, s, w)), jnp.float32)
    xs = rand((b, s, w))
    h0 = rand((b, w))
    got = _rglru_scan(xs, jnp.log(a), h0)
    h = h0
    refs = []
    for t in range(s):
        h = a[:, t] * h + xs[:, t]
        refs.append(h)
    ref = jnp.stack(refs, axis=1)
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_rglru_state_continuity():
    cfg = reduced_config(get_config("recurrentgemma-9b"))
    params = rglru_init(jax.random.PRNGKey(0), cfg)
    x = rand((2, 12, cfg.d_model))
    from repro.models.rglru import init_recurrent_state
    st0 = init_recurrent_state(2, cfg)
    y_full, _ = rglru_apply(params, cfg, x, state=st0)
    y1, st = rglru_apply(params, cfg, x[:, :7], state=st0)
    y2, _ = rglru_apply(params, cfg, x[:, 7:], state=st)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# SSD vs naive recurrence
# ---------------------------------------------------------------------------

def test_ssd_chunked_matches_naive_recurrence():
    b, s, h, p, n, chunk = 1, 12, 2, 4, 3, 4
    x = rand((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 1.5, (h,)), jnp.float32)
    B = rand((b, s, 1, n))
    C = rand((b, s, 1, n))
    y, final = _ssd_chunked(x, dt, a, B, C, chunk)

    S = np.zeros((b, h, p, n), np.float32)
    ys = []
    for t in range(s):
        da = np.exp(np.asarray(dt[:, t]) * np.asarray(a))  # (b, h)
        bx = np.einsum("bn,bhp->bhpn", np.asarray(B[:, t, 0]),
                       np.asarray(x[:, t]) * np.asarray(dt[:, t])[..., None])
        S = S * da[..., None, None] + bx
        ys.append(np.einsum("bn,bhpn->bhp", np.asarray(C[:, t, 0]), S))
    ref = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(final), S, atol=1e-3, rtol=1e-3)


def test_ssd_chunked_initial_state():
    b, s, h, p, n, chunk = 1, 8, 2, 4, 3, 4
    x = rand((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 1.5, (h,)), jnp.float32)
    B, C = rand((b, s, 1, n)), rand((b, s, 1, n))
    y_full, f_full = _ssd_chunked(x, dt, a, B, C, chunk)
    y1, st = _ssd_chunked(x[:, :4], dt[:, :4], a, B[:, :4], C[:, :4], chunk)
    y2, f2 = _ssd_chunked(x[:, 4:], dt[:, 4:], a, B[:, 4:], C[:, 4:], chunk,
                          s0=st)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(f2, f_full, atol=1e-3, rtol=1e-3)
