"""Plain float32 reference of the Granite-4.0-H hybrid decoder
(``granitemoehybrid``).

Written from the published model description (the Hugging Face
``GraniteMoeHybridForCausalLM`` modelling code and the Mamba-2 paper,
arXiv:2405.21060), in straightforward ``jax.numpy``, importing nothing
of the program:

    h   = E[tokens] * embedding_multiplier
    per layer (kind from layer_types):
        a = rmsnorm(h) * g_mix
        mamba:                                   # Mamba-2 mixer
            z, xBC, dt = a W_in                  # no bias
            xBC  = silu(causal_conv1d(xBC) + b_conv)     # width d_conv
            x, B, C = xBC                        # heads of d_head; groups
            dt   = softplus(dt + dt_bias)        # time_step_limit (0, inf):
                                                 # never clipped
            S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T  # A = -exp(A_log)
            y_t  = S_t C_t + D x_t               # one position at a time
            y    = rmsnorm(y * silu(z)) * g_gated   # over d_inner
            o    = y W_out
        attention:                               # GQA, no position embedding
            o = softmax(q k^T * attention_multiplier + causal) v  W_o
        h = h + o * residual_multiplier
        m = rmsnorm(h) * g_ff
        gates, idx = softmax(top_k(m W_router, k))   # over all router
                                                     # experts
        moe = sum over the held experts e of gate_e(m) *
              (silu(m Wg_e) * (m Wu_e)) Wd_e
        h = h + (moe + (silu(m Wsg) * (m Wsu)) Wsd) * residual_multiplier
    logits = (rmsnorm(h) * g_final) E^T / logits_scaling

The recurrence is the sequential scan of the published equations, not
the chunked algorithm.  Where the configuration cuts the experts to a
chip's share, only the held experts' terms are summed, as the program
computes them; the router keeps all its outputs.

Every matrix product runs at ``precision="highest"``, so on a TPU it is
float32 and not bf16 passes.  ``quant="fp8"`` is the control: every
matrix product takes its operands rounded to float8_e4m3 (each row of the
left and each column of the right operand scaled to the format's range),
the step below the bf16 that the configuration states.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _round_fp8(x, axis):
    """``x`` rounded to float8_e4m3, scaled along ``axis`` to its range."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(eq, a, b, quant, a_axis, b_axis):
    if quant == "fp8":
        a, b = _round_fp8(a, a_axis), _round_fp8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _attention(q, k, v, scale, quant):
    """Causal attention, queries in blocks of ``Q_BLOCK`` rows.
    q: (s, hq, hd); k, v: (s, hkv, hd); query head j reads KV head
    j // (hq / hkv)."""
    s, hq, hd = q.shape
    rep = hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = _mm("qhd,khd->hqk", qb, k, quant, -1, -1) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("hqk,khd->qhd", p, v, quant, -1, 0)

    return jax.lax.map(block, jnp.arange(s // Q_BLOCK)).reshape(s, hq, hd)


def _mamba(a, lw, d, quant):
    """The Mamba-2 mixer over one sequence ``a`` (s, D)."""
    s = a.shape[0]
    H, P, N, G = (d["mamba_n_heads"], d["mamba_d_head"], d["mamba_d_state"],
                  d["mamba_n_groups"])
    d_in, cw = H * P, d["mamba_d_conv"]
    zxbcdt = _mm("sd,df->sf", a, lw["in_proj"], quant, -1, 0)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    # causal depthwise conv: out[t] = sum_i w[i] * xbc[t - (cw - 1) + i]
    pad = jnp.pad(xbc, ((cw - 1, 0), (0, 0)))
    conv = sum(pad[i:i + s] * lw["conv_w"][i] for i in range(cw))
    xbc = jax.nn.silu(conv + lw["conv_b"])
    x, b, c = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    x = x.reshape(s, H, P)
    heads_per_group = H // G
    b = jnp.repeat(b.reshape(s, G, N), heads_per_group, axis=1)  # (s, H, N)
    c = jnp.repeat(c.reshape(s, G, N), heads_per_group, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                     # (s, H)
    a_neg = -jnp.exp(lw["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * a_neg)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", state, c_t, precision=HIGHEST)
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, b, c, dt))
    y = y + lw["D"][None, :, None] * x
    y = _rmsnorm(y.reshape(s, d_in) * jax.nn.silu(z), lw["gated_norm"],
                 d["rms_norm_eps"])
    return _mm("sf,fd->sd", y, lw["out_proj"], quant, -1, 0)


def _moe(m, lw, d, quant):
    """The held experts' share of the MoE plus the shared expert."""
    k, first = d["num_experts_per_tok"], d["first_held_expert"]
    held = d["num_local_experts"]
    logits = _mm("sd,de->se", m, lw["router"], quant, -1, 0)
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)                          # (s, k)
    experts = first + jnp.arange(held)
    gate_e = jnp.sum(jnp.where(idx[:, :, None] == experts, gates[..., None],
                               0.0), axis=1)                      # (s, held)
    g = _mm("sd,edf->esf", m, lw["w_gate"], quant, -1, 1)
    u = _mm("sd,edf->esf", m, lw["w_up"], quant, -1, 1)
    out = _mm("esf,efd->esd", jax.nn.silu(g) * u, lw["w_down"], quant, -1, 1)
    moe = jnp.einsum("se,esd->sd", gate_e, out, precision=HIGHEST)
    sg = _mm("sd,df->sf", m, lw["shared_gate"], quant, -1, 0)
    su = _mm("sd,df->sf", m, lw["shared_up"], quant, -1, 0)
    return moe + _mm("sf,fd->sd", jax.nn.silu(sg) * su, lw["shared_down"],
                     quant, -1, 0)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def logits_at(w: Dict, tokens, at, *, dims, quant: Optional[str] = None):
    """float32 logits ``(n, vocab)`` at positions ``at`` of one sequence.

    ``tokens``: (s,) int32 with ``s`` a multiple of ``Q_BLOCK``; causal, so
    padding after the last real token changes nothing before it.
    ``dims``: sorted ``(key, value)`` pairs of the configuration's sizes
    (``bench/models/granite.py``'s ``dims``)."""
    d = dict(dims)
    eps, res = d["rms_norm_eps"], d["residual_multiplier"]
    hq, hkv, hd = (d["num_attention_heads"], d["num_key_value_heads"],
                   d["head_dim"])
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    s = tokens.shape[0]
    h = f32(w["embed"][tokens]) * d["embedding_multiplier"]
    period = d["period"]
    for i, kind in enumerate(d["layer_types"]):
        pre = f"p{i % period}."
        lw = {k[len(pre):]: f32(x[i // period]) for k, x in w.items()
              if k.startswith(pre)}
        a = _rmsnorm(h, lw["mix_norm"], eps)
        if kind == "mamba":
            o = _mamba(a, lw, d, quant)
        else:
            def proj(name, heads):
                return _mm("sd,df->sf", a, lw[name], quant, -1, 0).reshape(
                    s, heads, hd)
            o = _attention(proj("wq", hq), proj("wk", hkv), proj("wv", hkv),
                           d["attention_multiplier"], quant)
            o = _mm("sf,fd->sd", o.reshape(s, hq * hd), lw["wo"], quant, -1,
                    0)
        h = h + o * res
        h = h + _moe(_rmsnorm(h, lw["ff_norm"], eps), lw, d, quant) * res
    h = _rmsnorm(h[at], f32(w["final_norm"]), eps)
    return (_mm("nd,vd->nv", h, f32(w["embed"]), quant, -1, -1)
            / d["logits_scaling"])
