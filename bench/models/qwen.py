"""The dense Qwen2 / Qwen3 decoder: GQA attention (Qwen2 with biases on
q, k and v, Qwen3 with a norm on each head of q and k), a SwiGLU MLP in
every layer, and a tied embedding.  The interface is described in
``bench/models/__init__.py``; the reference is ``bench/reference/qwen.py``.

Weights are stacked over layers.  Work counts use bf16 operands (2 bytes)
throughout, as the cells serve.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Tuple

import jax.numpy as jnp

from bench import weights
from bench.work import BYTES, Shape, gemm_flops

# The program's field that each published config.json key must equal.
PUBLISHED_TO_PROGRAM = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads":
    "num_kv_heads", "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings"}
# Architecture flags (``"architecture"`` in the configuration file) that
# the program's configuration carries under the same name.
FLAGS = ("qk_norm", "qkv_bias")


def dims(config: Dict) -> Dict:
    d = {k: config[k] for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "vocab_size",
        "rope_theta", "rms_norm_eps")}
    d["head_dim"] = config.get("head_dim", d["hidden_size"]
                               // d["num_attention_heads"])
    d.update(config["architecture"])
    return d


def program_config(config: Dict):
    """The program's registered ``arch`` with every cut key (``reduced``)
    at the value the configuration serves."""
    from repro.configs import get_config
    cut = {PUBLISHED_TO_PROGRAM[k]: config[k] for k in config["reduced"]}
    return dataclasses.replace(get_config(config["arch"]), **cut)


def differences(cfg, config: Dict) -> List[str]:
    want = dict(dims(config),
                tie_word_embeddings=config["tie_word_embeddings"])
    bad = [f"{k}: program {getattr(cfg, PUBLISHED_TO_PROGRAM[k])!r} != "
           f"configuration {want[k]!r}" for k in PUBLISHED_TO_PROGRAM
           if getattr(cfg, PUBLISHED_TO_PROGRAM[k]) != want[k]]
    bad += [f"{k}: program {getattr(cfg, k)!r} != {want[k]!r}"
            for k in FLAGS if getattr(cfg, k) != want[k]]
    return bad


# -- weights -----------------------------------------------------------------

def shapes(d: Dict) -> Dict[str, tuple]:
    L, h, hq, hkv, hd, f, V = (
        d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"],
        d["num_key_value_heads"], d["head_dim"], d["intermediate_size"],
        d["vocab_size"])
    s = {"embed": (V, h), "final_norm": (h,),
         "attn_norm": (L, h), "wq": (L, h, hq * hd), "wk": (L, h, hkv * hd),
         "wv": (L, h, hkv * hd), "wo": (L, hq * hd, h), "mlp_norm": (L, h),
         "w_gate": (L, h, f), "w_up": (L, h, f), "w_down": (L, f, h)}
    if d["qkv_bias"]:
        s.update(bq=(L, hq * hd), bk=(L, hkv * hd), bv=(L, hkv * hd))
    if d["qk_norm"]:
        s.update(q_norm=(L, hd), k_norm=(L, hd))
    return s


def make(d: Dict, seed: int, dtype=jnp.bfloat16):
    return weights.draw(shapes(d), seed, dtype)


def to_program(w: Dict, d: Dict) -> Dict:
    """The same arrays in the program's tree (no copies): one stacked
    block group ``b0`` of attention + MLP, the embedding tied."""
    def lin(wk, bk):
        p = {"w": w[wk]}
        if d["qkv_bias"] and bk:
            p["b"] = w[bk]
        return p

    mixer = {"wq": lin("wq", "bq"), "wk": lin("wk", "bk"),
             "wv": lin("wv", "bv"), "wo": {"w": w["wo"]}}
    if d["qk_norm"]:
        mixer["q_norm"] = {"scale": w["q_norm"]}
        mixer["k_norm"] = {"scale": w["k_norm"]}
    block = {"norm_mix": {"scale": w["attn_norm"]}, "mixer": mixer,
             "norm_ff": {"scale": w["mlp_norm"]},
             "ff": {"w_gate": {"w": w["w_gate"]}, "w_up": {"w": w["w_up"]},
                    "w_down": {"w": w["w_down"]}}}
    return {"embed": {"table": w["embed"]},
            "blocks": {"groups": {"b0": block}, "rem": []},
            "final_norm": {"scale": w["final_norm"]}}


# -- work --------------------------------------------------------------------

def layer_gemms(d: Dict, m: int) -> List[Shape]:
    """The dense matmuls of one decoder layer on ``m`` rows."""
    h, hq, hkv, hd, f = (d["hidden_size"], d["num_attention_heads"],
                         d["num_key_value_heads"], d["head_dim"],
                         d["intermediate_size"])
    return [(m, h, hq * hd), (m, h, hkv * hd), (m, h, hkv * hd),
            (m, hq * hd, h), (m, h, f), (m, h, f), (m, f, h)]


def step_gemms(d: Dict, m: int, unembed_rows: int) -> List[Shape]:
    """Every dense matmul of one forward over ``m`` rows, with the tied
    unembedding on the ``unembed_rows`` rows whose logits are read."""
    return (layer_gemms(d, m) * d["num_hidden_layers"]
            + [(unembed_rows, d["hidden_size"], d["vocab_size"])])


def attention_layers(d: Dict) -> int:
    """Every layer attends."""
    return d["num_hidden_layers"]


def decode_attention(d: Dict, contexts: Iterable[int]) -> Tuple[float,
                                                                float]:
    """(operations, bytes) of one layer's paged decode attention over live
    slots whose caches hold ``contexts`` tokens (the new one included):
    scores and the weighted sum over every live token, K and V of those
    tokens read once, q read and the output written once per slot."""
    hq, hkv, hd = (d["num_attention_heads"], d["num_key_value_heads"],
                   d["head_dim"])
    flops = nbytes = 0.0
    for c in contexts:
        flops += 4.0 * c * hq * hd
        nbytes += BYTES * (2 * c * hkv * hd + 2 * hq * hd)
    return flops, nbytes


def causal_attention_flops(d: Dict, length: int) -> float:
    """Scores and weighted sum of causal attention over ``length`` tokens,
    one layer: each query attends to itself and the tokens before it."""
    hq, hd = d["num_attention_heads"], d["head_dim"]
    return 4.0 * hq * hd * length * (length + 1) / 2


def prefill_flops(d: Dict, length: int) -> float:
    """Model operations of one prefill: every layer on every prompt token,
    and the logits of the last position only."""
    mm = sum(gemm_flops(s) for s in step_gemms(d, length, 1))
    return mm + attention_layers(d) * causal_attention_flops(d, length)


def decode_flops(d: Dict, contexts: List[int]) -> float:
    """Model operations of one decode step over live slots."""
    mm = sum(gemm_flops(s) for s in step_gemms(d, len(contexts),
                                               len(contexts)))
    att, _ = decode_attention(d, contexts)
    return mm + attention_layers(d) * att
