"""Granite-4.0-H (``granitemoehybrid``): a hybrid decoder whose layers
follow ``layer_types``, each a Mamba-2 mixer or a GQA attention mixer
with no position embedding, and each followed by a MoE of SwiGLU experts
(softmax over the kept top-k router logits) plus a shared SwiGLU expert;
the embedding times ``embedding_multiplier``, every residual branch times
``residual_multiplier``, attention scores times ``attention_multiplier``,
logits over ``logits_scaling``; a tied embedding.  The interface is
described in ``bench/models/__init__.py``; the reference is
``bench/reference/granite.py``.

A configuration may cut the experts to the chip's share of a deployment:
``num_local_experts`` is then how many this chip holds, from
``first_held_expert``, while the router keeps the published width
(``published["num_local_experts"]``).

Weights are stacked over the periods of ``layer_types``: leaf
``p<j>.<name>`` holds layer ``j`` of every period, so the program's
stacked block ``b<j>`` takes the same array.  Work counts use bf16
operands (2 bytes), as the cells serve, and float32 for dt and the SSM
state, as the program keeps them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp

from bench import weights
from bench.work import BYTES, Shape, gemm_flops

F32 = 4
KINDS = {"mamba": "ssm", "attention": "attn"}
# Published config.json key -> the program's field that must equal it.
PUBLISHED_TO_PROGRAM = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads":
    "num_kv_heads", "intermediate_size": "d_ff",
    "shared_intermediate_size": "shared_expert_d_ff",
    "num_local_experts": "held_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "mamba_d_head": "ssm_head_dim", "mamba_d_state": "ssm_state",
    "mamba_n_groups": "ssm_ngroups", "mamba_d_conv": "conv1d_width",
    "mamba_expand": "ssm_expand", "mamba_chunk_size": "ssm_chunk",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "attention_multiplier": "attention_multiplier",
    "logits_scaling": "logits_scaling"}
# Published values that the program's structure states, each with the
# field (and value) that must hold.
STRUCTURE = {
    "position_embedding_type": ("nope", "rope", False),
    "hidden_act": ("silu", "mlp_act", "silu"),
    "attention_bias": (False, "qkv_bias", False),
    "normalization_function": ("rmsnorm", "norm_type", "rmsnorm"),
}


def period_of(types: List[str]) -> int:
    """The shortest prefix of ``types`` that repeats to all of it."""
    n = len(types)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and types == types[:p] * (n // p))


def dims(config: Dict) -> Dict:
    d = {k: config[k] for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "intermediate_size",
        "shared_intermediate_size", "num_local_experts",
        "num_experts_per_tok", "vocab_size", "rms_norm_eps", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
        "mamba_expand", "mamba_chunk_size", "embedding_multiplier",
        "residual_multiplier", "attention_multiplier", "logits_scaling")}
    d["head_dim"] = d["hidden_size"] // d["num_attention_heads"]
    d["layer_types"] = list(config["layer_types"])
    d["period"] = period_of(d["layer_types"])
    d["router_experts"] = config.get("published", {}).get(
        "num_local_experts", config["num_local_experts"])
    d["first_held_expert"] = config.get("first_held_expert", 0)
    return d


def program_config(config: Dict):
    """The program's registered ``arch`` with the cut depth and the
    experts this chip holds."""
    from repro.configs import get_config
    d = dims(config)
    cfg = get_config(config["arch"])
    held = d["num_local_experts"]
    return dataclasses.replace(
        cfg, num_layers=d["num_hidden_layers"],
        experts_held=0 if held == cfg.num_experts else held,
        first_expert_held=d["first_held_expert"])


def differences(cfg, config: Dict) -> List[str]:
    d = dims(config)
    want = dict(d, tie_word_embeddings=config["tie_word_embeddings"])
    bad = [f"{k}: program {getattr(cfg, f)!r} != configuration {want[k]!r}"
           for k, f in PUBLISHED_TO_PROGRAM.items()
           if getattr(cfg, f) != want[k]]
    checks = [
        ("head_dim", cfg.head_dim, d["head_dim"]),
        ("mamba_n_heads", cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim,
         d["mamba_n_heads"]),
        ("router experts", cfg.num_experts, d["router_experts"]),
        ("first held expert", cfg.first_expert_held,
         d["first_held_expert"]),
        ("layer_types", [cfg.block_pattern[i % len(cfg.block_pattern)]
                         for i in range(cfg.num_layers)],
         [KINDS[t] for t in d["layer_types"]])]
    checks += [(k, getattr(cfg, f), v) for k, (pub, f, v) in STRUCTURE.items()
               if config[k] == pub]
    bad += [f"{k}: program {got!r} != {exp!r}" for k, got, exp in checks
            if got != exp]
    bad += [f"{k}: {config[k]!r} is not what the program serves"
            for k, (pub, _, _) in STRUCTURE.items() if config[k] != pub]
    if not config["mamba_conv_bias"] or config["mamba_proj_bias"]:
        bad.append("mamba_conv_bias, mamba_proj_bias: the program's conv "
                   "always has a bias, its projections never")
    return bad


# -- weights -----------------------------------------------------------------

def _sizes(d: Dict):
    D, H, P, N, G = (d["hidden_size"], d["mamba_n_heads"], d["mamba_d_head"],
                     d["mamba_d_state"], d["mamba_n_groups"])
    d_in = H * P
    conv = d_in + 2 * G * N
    return D, H, P, N, G, d_in, conv, 2 * d_in + 2 * G * N + H


def shapes(d: Dict) -> Tuple[Dict[str, tuple], Dict[str, tuple]]:
    """(leaves drawn by ``bench.weights.draw``, leaves drawn here: the
    embedding and the Mamba scalars), each ``name -> shape``."""
    D, H, P, N, G, d_in, conv, proj = _sizes(d)
    n = d["num_hidden_layers"] // d["period"]
    hq, hkv, hd = (d["num_attention_heads"], d["num_key_value_heads"],
                   d["head_dim"])
    E, F, Fs = (d["num_local_experts"], d["intermediate_size"],
                d["shared_intermediate_size"])
    std = {"final_norm": (D,)}
    special = {"embed": (d["vocab_size"], D)}
    for j, kind in enumerate(d["layer_types"][:d["period"]]):
        p = f"p{j}."
        std.update({
            p + "mix_norm": (n, D), p + "ff_norm": (n, D),
            p + "router": (n, D, d["router_experts"]),
            p + "w_gate": (n, E, D, F), p + "w_up": (n, E, D, F),
            p + "w_down": (n, E, F, D), p + "shared_gate": (n, D, Fs),
            p + "shared_up": (n, D, Fs), p + "shared_down": (n, Fs, D)})
        if kind == "mamba":
            std.update({p + "in_proj": (n, D, proj),
                        p + "out_proj": (n, d_in, D),
                        p + "conv_w": (n, d["mamba_d_conv"], conv),
                        p + "gated_norm": (n, d_in)})
            special.update({p + "conv_b": (n, conv), p + "A_log": (n, H),
                            p + "dt_bias": (n, H), p + "D": (n, H)})
        else:
            std.update({p + "wq": (n, D, hq * hd), p + "wk": (n, D, hkv * hd),
                        p + "wv": (n, D, hkv * hd), p + "wo": (n, hq * hd, D)})
    return std, special


def _draw_own(special: Dict[str, tuple], seed: int, dtype,
              embedding_multiplier: float):
    """The leaves ``bench.weights.draw`` does not draw as this model
    needs them.

    The embedding at ``EMBED_STD / embedding_multiplier``, so the
    residual stream starts at the scale the other families give it.
    Drawn at ``EMBED_STD``, a token's own embedding (times 12) would
    dominate the tied logits at every layer's output scale, every served
    token would repeat its input, and the output check could not tell a
    sound program from a broken one.

    The Mamba scalars in their published init ranges: A = -exp(A_log)
    with exp(A_log) ~ U[1, 16]; dt_bias the inverse softplus of dt
    log-uniform in [0.001, 0.1]; D around 1; the conv bias around 0.  So
    the state decays over up to ~1,000 tokens and a lost state row or
    conv tail shows in the check."""
    names = sorted(special)

    def one(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shp, kind = special[name], name.split(".")[-1]
            if kind == "embed":
                x = jax.random.normal(k, shp, dtype) * jnp.asarray(
                    weights.EMBED_STD / embedding_multiplier, dtype)
            elif kind == "A_log":
                x = jnp.log(jax.random.uniform(k, shp, jnp.float32, 1, 16))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shp, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
                x = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == "D":
                x = 1 + weights.NORM_STD * jax.random.normal(k, shp)
            else:
                x = weights.BIAS_STD * jax.random.normal(k, shp)
            out[name] = x.astype(dtype)
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(weights.jax_seed(seed)), 1)
    return jax.jit(one)(key)


def make(d: Dict, seed: int, dtype=jnp.bfloat16):
    std, special = shapes(d)
    return dict(weights.draw(std, seed, dtype),
                **_draw_own(special, seed, dtype,
                            d["embedding_multiplier"]))


def to_program(w: Dict, d: Dict) -> Dict:
    """The same arrays in the program's tree (no copies): one stacked
    block ``b<j>`` per layer of the period, the embedding tied."""
    blocks = {}
    for j, kind in enumerate(d["layer_types"][:d["period"]]):
        p = f"p{j}."
        if kind == "mamba":
            mixer = {"in_proj": {"w": w[p + "in_proj"]},
                     "out_proj": {"w": w[p + "out_proj"]},
                     "conv_w": w[p + "conv_w"], "conv_b": w[p + "conv_b"],
                     "A_log": w[p + "A_log"], "D": w[p + "D"],
                     "dt_bias": w[p + "dt_bias"],
                     "norm": {"scale": w[p + "gated_norm"]}}
        else:
            mixer = {k: {"w": w[p + k]} for k in ("wq", "wk", "wv", "wo")}
        ff = {"router": {"w": w[p + "router"]},
              "w_gate": {"w": w[p + "w_gate"]}, "w_up": {"w": w[p + "w_up"]},
              "w_down": {"w": w[p + "w_down"]},
              "shared": {"w_gate": {"w": w[p + "shared_gate"]},
                         "w_up": {"w": w[p + "shared_up"]},
                         "w_down": {"w": w[p + "shared_down"]}}}
        blocks[f"b{j}"] = {"norm_mix": {"scale": w[p + "mix_norm"]},
                           "mixer": mixer,
                           "norm_ff": {"scale": w[p + "ff_norm"]}, "ff": ff}
    return {"embed": {"table": w["embed"]},
            "blocks": {"groups": blocks, "rem": []},
            "final_norm": {"scale": w["final_norm"]}}


# -- work --------------------------------------------------------------------

def _count(d: Dict, kind: str) -> int:
    return sum(t == kind for t in d["layer_types"])


def step_gemms(d: Dict, m: int, unembed_rows: int) -> List[Shape]:
    """Every dense matmul (``gemm`` family) of one forward over ``m``
    rows: the Mamba-2 in and out projections, the attention projections,
    the shared expert, and the tied unembedding on the ``unembed_rows``
    rows whose logits are read.  The router and the held experts are not
    ``gemm`` work."""
    D, H, P, N, G, d_in, conv, proj = _sizes(d)
    hq, hkv, hd, Fs = (d["num_attention_heads"], d["num_key_value_heads"],
                       d["head_dim"], d["shared_intermediate_size"])
    out: List[Shape] = []
    for kind in d["layer_types"]:
        if kind == "mamba":
            out += [(m, D, proj), (m, d_in, D)]
        else:
            out += [(m, D, hq * hd), (m, D, hkv * hd), (m, D, hkv * hd),
                    (m, hq * hd, D)]
        out += [(m, D, Fs), (m, D, Fs), (m, Fs, D)]
    return out + [(unembed_rows, D, d["vocab_size"])]


def attention_layers(d: Dict) -> int:
    """The attention mixers (one in each period of ten here)."""
    return _count(d, "attention")


def decode_attention(d: Dict, contexts: Iterable[int]) -> Tuple[float,
                                                                float]:
    """(operations, bytes) of one attention layer's paged decode over live
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
    one layer."""
    hq, hd = d["num_attention_heads"], d["head_dim"]
    return 4.0 * hq * hd * length * (length + 1) / 2


def expert_work(d: Dict, rows: float, hit: float) -> Tuple[float, float]:
    """(operations, bytes) of the held experts' SwiGLU GEMMs over ``rows``
    token rows routed to them, in which ``hit`` experts had a row (both
    summed over layers and steps): gate, up and down on every row; each
    expert hit reads its three matrices once, and each row reads its
    input and writes its output of each GEMM once."""
    D, F = d["hidden_size"], d["intermediate_size"]
    flops = rows * 3 * gemm_flops((1, D, F))
    nbytes = BYTES * (hit * 3 * D * F + rows * (3 * D + 3 * F))
    return flops, nbytes


def expected_expert_rows(d: Dict, tokens: float) -> float:
    """Rows a forward over ``tokens`` tokens routes to the held experts,
    expected under uniform routing: k x held / router width a token, in
    every layer.  Expected, not counted: the readers count the rows."""
    return (tokens * d["num_experts_per_tok"] * d["num_local_experts"]
            / d["router_experts"] * d["num_hidden_layers"])


def ssd_prefill_work(d: Dict, length: int) -> Tuple[float, float]:
    """(operations, bytes) of the chunked SSD scans of one prefill of
    ``length`` tokens, over every Mamba-2 layer: what the model needs, at
    the exact length in chunks of ``mamba_chunk_size`` (the last one
    ragged), not the decay matrices an implementation materialises.  Per
    chunk of q tokens: C.B scores per group and the causal-masked
    weighted sum of x per head over the q(q+1)/2 pairs, the chunk's state
    B^T x and the carried state's output C.S per head.  Bytes: x, B, C
    (bf16) and dt (float32) in, y (bf16) out, the final state (float32)
    out."""
    _, H, P, N, G, _, _, _ = _sizes(d)
    Q = d["mamba_chunk_size"]
    flops = 0.0
    for start in range(0, length, Q):
        q = min(Q, length - start)
        pairs = q * (q + 1) / 2
        flops += 2.0 * pairs * (G * N + H * P) + 4.0 * q * H * P * N
    nbytes = (length * (BYTES * (2 * H * P + 2 * G * N) + F32 * H)
              + F32 * H * P * N)
    layers = _count(d, "mamba")
    return layers * flops, layers * nbytes


def _per_token_flops(d: Dict) -> float:
    """Operations of one token in every layer that are neither a dense
    matmul nor attention over the context: the router, the expected
    expert rows, the conv, and (for decode) one recurrent SSD step."""
    D, H, P, N, G, _, conv, _ = _sizes(d)
    router = 2.0 * D * d["router_experts"] * d["num_hidden_layers"]
    experts = expert_work(d, expected_expert_rows(d, 1), 0)[0]
    mamba = _count(d, "mamba") * 2.0 * d["mamba_d_conv"] * conv
    return router + experts + mamba


def prefill_flops(d: Dict, length: int) -> float:
    """Model operations of one prefill: every layer on every prompt token
    (experts at the expected rows), the SSD scans, and the logits of the
    last position only."""
    mm = sum(gemm_flops(s) for s in step_gemms(d, length, 1))
    att = attention_layers(d) * causal_attention_flops(d, length)
    return (mm + att + length * _per_token_flops(d)
            + ssd_prefill_work(d, length)[0])


def decode_flops(d: Dict, contexts: List[int]) -> float:
    """Model operations of one decode step over live slots (experts at
    the expected rows): per SSD layer and slot, the state's decay, the
    B x dt update and the output C.S (5 H P N)."""
    n = len(contexts)
    _, H, P, N, _, _, _, _ = _sizes(d)
    mm = sum(gemm_flops(s) for s in step_gemms(d, n, n))
    att, _ = decode_attention(d, contexts)
    ssd = _count(d, "mamba") * 5.0 * H * P * N
    return mm + attention_layers(d) * att + n * (_per_token_flops(d) + ssd)
