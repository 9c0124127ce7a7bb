"""Operations, bytes and least seconds, from shapes alone, and the
table of peaks.

These are the numerators of every roofline share and of the model's
utilisation.  What a model needs (its matmul shapes, its attention's
operations and bytes) is counted by its family module
(``bench/models/``); this module holds what every family shares: a
matmul's operations and bytes (A, B and C once), the least time of a
piece of work at the chip's peaks, and the peaks themselves.  bf16
operands (2 bytes) throughout, as the cells serve.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

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
