"""Operations and bytes that the algorithm needs, from shapes alone.

These are the numerators of every roofline share and of the model's
utilisation.  They count the work of the model as published, never what
an implementation pads or recomputes: live rows only, live cache tokens
only, causal halves of attention, weights read once per call.  bf16
operands (2 bytes) throughout, as the cells serve.

``dims`` is the model block of a configuration file (hidden size, heads,
head size, intermediate size, vocabulary, layers).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Tuple

BYTES = 2  # bf16 operands and results

Shape = Tuple[int, int, int]  # (M, K, N)


def load_peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


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


def gemm_flops(s: Shape) -> float:
    m, k, n = s
    return 2.0 * m * k * n


def gemm_bytes(s: Shape) -> float:
    m, k, n = s
    return float(BYTES * (m * k + k * n + m * n))


def min_seconds(flops: float, nbytes: float, peaks: Dict) -> Tuple[float,
                                                                  str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def gemm_min_seconds(shapes: Iterable[Shape], peaks: Dict) -> float:
    """The least seconds of all ``shapes``, each at its own bound."""
    return sum(min_seconds(gemm_flops(s), gemm_bytes(s), peaks)[0]
               for s in shapes)


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
    return mm + d["num_hidden_layers"] * causal_attention_flops(d, length)


def decode_flops(d: Dict, contexts: List[int]) -> float:
    """Model operations of one decode step over live slots."""
    mm = sum(gemm_flops(s) for s in step_gemms(d, len(contexts),
                                               len(contexts)))
    att, _ = decode_attention(d, contexts)
    return mm + d["num_hidden_layers"] * att
