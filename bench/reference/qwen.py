"""Plain float32 reference of the Qwen2 / Qwen3 dense decoder.

Written from the published model descriptions (the Hugging Face
``Qwen2ForCausalLM`` and ``Qwen3ForCausalLM`` modelling code), in
straightforward ``jax.numpy``, importing nothing of the program:

    h   = E[tokens]
    per layer:
        a    = rmsnorm(h) * g_attn
        q, k, v = a Wq (+ bq), a Wk (+ bk), a Wv (+ bv)   # Qwen2: biases
        q, k = rmsnorm(q) * g_q, rmsnorm(k) * g_k        # Qwen3: per head
        q, k = rope(q), rope(k)                          # rotate-half
        o    = softmax(q k^T / sqrt(hd) + causal) v      # query head j
                                                         # reads KV head
                                                         # j // (hq / hkv)
        h    = h + o Wo
        m    = rmsnorm(h) * g_mlp
        h    = h + (silu(m Wg) * (m Wu)) Wd
    logits = (rmsnorm(h) * g_final) E^T                  # tied embedding

Every matrix product runs at ``precision="highest"``, so on a TPU it is
float32 and not bf16 passes.  Weights are upcast one layer at a time
inside a scan, so the reference fits beside the model's bf16 weights.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8_e4m3 (each row of the left and each column of the right
operand scaled to the format's range), the step below the bf16 that the
configurations state.
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


def _rope(x, pos, theta):
    """x: (s, h, hd); rotate-half RoPE at integer positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, quant):
    """Causal attention, queries in blocks of ``Q_BLOCK`` rows.
    q: (s, hq, hd); k, v: (s, hkv, hd)."""
    s, hq, hd = q.shape
    rep = hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    nb = s // Q_BLOCK
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = _mm("qhd,khd->hqk", qb, k, quant, -1, -1) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("hqk,khd->qhd", p, v, quant, -1, 0)

    return jax.lax.map(block, jnp.arange(nb)).reshape(s, hq, hd)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def logits_at(w: Dict, tokens, at, *, dims, quant: Optional[str] = None):
    """float32 logits ``(n, vocab)`` at positions ``at`` of one sequence.

    ``tokens``: (s,) int32 with ``s`` a multiple of ``Q_BLOCK``; causal, so
    padding after the last real token changes nothing before it.
    ``dims``: sorted ``(key, value)`` pairs of the configuration's sizes
    and ``qk_norm`` / ``qkv_bias``."""
    d = dict(dims)
    hq, hkv, hd = (d["num_attention_heads"], d["num_key_value_heads"],
                   d["head_dim"])
    eps, theta = d["rms_norm_eps"], d["rope_theta"]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    s = tokens.shape[0]
    pos = jnp.arange(s)
    h = f32(w["embed"][tokens])
    layer_keys = [k for k in w if k not in ("embed", "final_norm")]

    def layer(h, lw):
        lw = {k: f32(x) for k, x in lw.items()}
        a = _rmsnorm(h, lw["attn_norm"], eps)

        def proj(wk, bk, heads):
            y = _mm("sd,df->sf", a, lw[wk], quant, -1, 0)
            if d["qkv_bias"]:
                y = y + lw[bk]
            return y.reshape(s, heads, hd)

        q, k, v = proj("wq", "bq", hq), proj("wk", "bk", hkv), \
            proj("wv", "bv", hkv)
        if d["qk_norm"]:
            q = _rmsnorm(q, lw["q_norm"], eps)
            k = _rmsnorm(k, lw["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q, k, v, quant).reshape(s, hq * hd)
        h = h + _mm("sf,fd->sd", o, lw["wo"], quant, -1, 0)
        m = _rmsnorm(h, lw["mlp_norm"], eps)
        g = _mm("sd,df->sf", m, lw["w_gate"], quant, -1, 0)
        u = _mm("sd,df->sf", m, lw["w_up"], quant, -1, 0)
        h = h + _mm("sf,fd->sd", jax.nn.silu(g) * u, lw["w_down"], quant,
                    -1, 0)
        return h, None

    h, _ = jax.lax.scan(layer, h, {k: w[k] for k in layer_keys})
    h = _rmsnorm(h[at], f32(w["final_norm"]), eps)
    return _mm("nd,vd->nv", h, f32(w["embed"]), quant, -1, -1)
