"""The output check that decides ``correct``.

After the window has closed, a sample of the requests the window
finished, drawn from the seed and always holding the one with the most
served tokens, is run once through the plain float32 reference over
prompt + served tokens (teacher forcing).  For every served token the
gap is the reference's best logit at that position minus the reference's
logit of the served token; the number compared is the widest gap.  A
greedy server that computes what the reference computes serves tokens
whose gap is rounding; a wrong page, mask, position, weight or token
serves tokens far below the best.

The control (``control.py``) reads the same number for the token that the
fp8 reference puts first at each position.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

Served = Tuple[np.ndarray, np.ndarray]  # (prompt, served tokens)
WIDTH_BLOCK = 512  # a multiple of every reference's query block


def sample(finished: Dict[int, Served], seed: int, tokens: int
           ) -> List[int]:
    """Request ids to check: the one with the most served tokens, then
    others in a seed-drawn order until ``tokens`` served tokens."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r][1]),
                                       len(finished[r][0]), -r))
    order = [longest] + [r for r in np.random.default_rng(seed + 1)
                         .permutation(rids) if r != longest]
    out, n = [], 0
    for r in order:
        if n >= tokens:
            break
        out.append(int(r))
        n += len(finished[r][1])
    return out


def reference_module(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def dims_key(dims: Dict) -> tuple:
    return tuple(sorted(dims.items()))


def teacher_forced(prompt: np.ndarray, served: np.ndarray, n_at: int):
    """Prompt and served tokens padded to whole ``WIDTH_BLOCK``s, and the
    positions that predicted each served token, padded to ``n_at`` with
    the last one: so a cell's reference runs a few compiled shapes."""
    n = len(prompt) + len(served) - 1
    width = -(-n // WIDTH_BLOCK) * WIDTH_BLOCK
    seq = np.zeros(width, np.int32)
    seq[:len(prompt)] = prompt
    seq[len(prompt):n] = served[:-1]
    at = np.full(n_at, n - 1, np.int32)
    at[:len(served)] = np.arange(len(served)) + len(prompt) - 1
    return jnp.asarray(seq), jnp.asarray(at)


def gaps(ref, weights, dims: Dict, prompt, served, n_at: int, pick=None
         ) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit of
    the token checked (``served``, or ``pick(tokens, at)``'s choice)."""
    seq, at = teacher_forced(prompt, served, n_at)
    lg = ref.logits_at(weights, seq, at, dims=dims_key(dims))
    padded = np.zeros(n_at, np.int32)
    padded[:len(served)] = served
    chosen = jnp.asarray(padded) if pick is None else pick(seq, at)
    mine = jnp.take_along_axis(lg, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(lg.max(axis=-1) - mine)[:len(served)]


def widest_gap(ref, weights, dims: Dict, finished: Dict[int, Served],
               rids: Sequence[int], n_at: int) -> Tuple[float, int, int]:
    """(widest gap, tokens checked, tokens whose gap is 0)."""
    worst, n, exact = 0.0, 0, 0
    for r in rids:
        prompt, served = finished[r]
        g = gaps(ref, weights, dims, prompt, served, n_at)
        worst = max(worst, float(g.max()))
        n += len(g)
        exact += int((g == 0).sum())
    return worst, n, exact


def judge(readings: Dict[str, Tuple[float, float]]) -> bool:
    """Every number compared is at or below its limit."""
    return all(v <= lim for v, lim in readings.values())

