"""Weights made by the benchmark from the seed, and their hand-over to the
program.

The weights belong to the benchmark, not to the program: the reference
reads them as they are made here, and the program is handed the same
arrays in its own parameter layout.  So the reference takes nothing that
the program made.

They are made on the device in one jitted call, in bf16 (the type they
are served in), stacked over layers.  Norm scales are drawn around 1 and
biases around 0 with a visible spread, so a program that dropped either
would not agree with the reference.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.1
BIAS_STD = 0.5


def _shapes(d: Dict, arch: Dict) -> Dict[str, tuple]:
    L, h, hq, hkv, hd, f, V = (
        d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"],
        d["num_key_value_heads"], d["head_dim"], d["intermediate_size"],
        d["vocab_size"])
    s = {"embed": (V, h), "final_norm": (h,),
         "attn_norm": (L, h), "wq": (L, h, hq * hd), "wk": (L, h, hkv * hd),
         "wv": (L, h, hkv * hd), "wo": (L, hq * hd, h), "mlp_norm": (L, h),
         "w_gate": (L, h, f), "w_up": (L, h, f), "w_down": (L, f, h)}
    if arch["qkv_bias"]:
        s.update(bq=(L, hq * hd), bk=(L, hkv * hd), bv=(L, hkv * hd))
    if arch["qk_norm"]:
        s.update(q_norm=(L, hd), k_norm=(L, hd))
    return s


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.default_rng(seed).integers(2 ** 31 - 1))


def make(d: Dict, arch: Dict, seed: int, dtype=jnp.bfloat16
         ) -> Dict[str, jax.Array]:
    """All weights of one model, drawn on the device in one call."""
    shapes = _shapes(d, arch)
    names = sorted(shapes)

    def draw(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shp = shapes[name]
            z = jax.random.normal(k, shp, dtype)
            if name == "embed":
                out[name] = z * jnp.asarray(EMBED_STD, dtype)
            elif name.endswith("norm"):
                out[name] = 1 + z * jnp.asarray(NORM_STD, dtype)
            elif name.startswith("b"):
                out[name] = z * jnp.asarray(BIAS_STD, dtype)
            else:  # (..., fan_in, fan_out) matrices
                out[name] = z * jnp.asarray(shp[-2] ** -0.5, dtype)
        return out

    return jax.jit(draw)(jax.random.PRNGKey(jax_seed(seed)))


def to_program(w: Dict[str, jax.Array], arch: Dict) -> Dict:
    """The same arrays in the program's parameter tree (no copies)."""
    def lin(wk, bk):
        p = {"w": w[wk]}
        if arch["qkv_bias"] and bk:
            p["b"] = w[bk]
        return p

    mixer = {"wq": lin("wq", "bq"), "wk": lin("wk", "bk"),
             "wv": lin("wv", "bv"), "wo": {"w": w["wo"]}}
    if arch["qk_norm"]:
        mixer["q_norm"] = {"scale": w["q_norm"]}
        mixer["k_norm"] = {"scale": w["k_norm"]}
    block = {"norm_mix": {"scale": w["attn_norm"]}, "mixer": mixer,
             "norm_ff": {"scale": w["mlp_norm"]},
             "ff": {"w_gate": {"w": w["w_gate"]}, "w_up": {"w": w["w_up"]},
                    "w_down": {"w": w["w_down"]}}}
    return {"embed": {"table": w["embed"]},
            "blocks": {"groups": {"b0": block}, "rem": []},
            "final_norm": {"scale": w["final_norm"]}}


def check_layout(params: Dict, expected) -> None:
    """Refuse a hand-over whose tree, shapes or dtypes differ from the
    program's own (``expected``: the program's parameter shapes)."""
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), expected)
    if got != want:
        raise ValueError(f"weights do not fit the program's layout:\n"
                         f"made {got}\nprogram wants {want}")
