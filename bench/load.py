"""Open-loop request schedules, built from a traffic file and a seed.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

  * ``arrival``: ``"poisson"`` (requests fall due at ``rate_per_s`` on the
    wall clock, whether or not earlier ones finished) or ``"backlog"``
    (``backlog_requests`` are all due before the window opens);
  * ``lead_in_s``: traffic served before the window opens, so that the
    window starts with the batch already churning;
  * ``prompt_tokens`` / ``answer_tokens``: lognormal lengths given by
    ``median`` and ``sigma``, clipped to ``[min, max]``; prompts are
    rounded up to a multiple of ``round_up_to`` (the prompt ladder);
  * ``stratum`` (backlog only): how many queued requests form one
    complete set of lengths, so that any stretch of the queue a window
    serves holds the same mix;
  * ``check_served_tokens``: how many served tokens the output check reads.

Every seed serves the same set of gaps and lengths, in another order:
the ``n`` requests of a span take the quantiles ``(i + 0.5) / n`` of the
distributions, and ``--seed`` draws their order and the token ids (and
the weights).  Independent draws would change how much work a window
holds from seed to seed, and with it the tails.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due: float          # seconds from window open (negative: lead-in)
    prompt: np.ndarray  # (L,) int32
    max_new: int
    in_window: bool     # due inside the measured window


def lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    """The ``n`` mid-quantiles of a clipped lognormal, rounded up."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    step = spec.get("round_up_to", 1)
    return (np.ceil(x / step) * step).astype(np.int64)


def ladder(spec: dict) -> List[int]:
    """Every prompt length the traffic can draw."""
    step = spec.get("round_up_to", 1)
    lo = -(-spec["min"] // step) * step
    return list(range(lo, spec["max"] + 1, step))


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    """The ``n`` mid-quantiles of the gaps at ``rate``: they sum to just
    under ``n / rate``, so that every request of a span of that length
    falls inside it."""
    return -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate


def _strata(rng, n: int, size: int, make) -> np.ndarray:
    """``n`` values in consecutive strata of ``size``, each a complete
    quantile set ``make(size)`` in a seed-drawn order."""
    out = []
    while len(out) < n:
        out.extend(rng.permutation(make(size)))
    return np.asarray(out[:n])


def build(traffic: dict, seed: int, seconds: float, vocab: int
          ) -> List[Planned]:
    """The schedule of one run, sorted by due time."""
    rng = np.random.default_rng((seed, 0))
    ids = np.random.default_rng(seed)
    lead = float(traffic.get("lead_in_s", 0.0))
    pspec, aspec = traffic["prompt_tokens"], traffic["answer_tokens"]
    if traffic["arrival"] == "poisson":
        rate = float(traffic["rate_per_s"])
        n_lead = int(round(rate * lead))
        n_win = int(round(rate * seconds))
        parts = []
        for n, start, span in ((n_lead, -lead, lead), (n_win, 0.0, seconds)):
            if not n:
                continue
            gaps = rng.permutation(exponential_quantiles(n, n / span))
            due = start + np.cumsum(gaps) - gaps
            plen = rng.permutation(lognormal_quantiles(n, pspec))
            alen = rng.permutation(lognormal_quantiles(n, aspec))
            parts.append((due, plen, alen, start == 0.0))
    elif traffic["arrival"] == "backlog":
        n = int(traffic["backlog_requests"])
        size = int(traffic["stratum"])
        plen = _strata(rng, n, size, lambda k: lognormal_quantiles(k, pspec))
        alen = _strata(rng, n, size, lambda k: lognormal_quantiles(k, aspec))
        due = np.full(n, -lead)
        parts = [(due, plen, alen, False)]
    else:
        raise ValueError(f"unknown arrival {traffic['arrival']!r}")

    out: List[Planned] = []
    for due, plen, alen, in_window in parts:
        for d, L, a in zip(due, plen, alen):
            out.append(Planned(
                rid=len(out), due=float(d),
                prompt=ids.integers(0, vocab, int(L)).astype(np.int32),
                max_new=int(a), in_window=in_window))
    out.sort(key=lambda p: (p.due, p.rid))
    return out


def describe(plan: List[Planned]) -> str:
    """One line: counts and length totals of the window's requests."""
    win = [p for p in plan if p.in_window]
    pool = win or plan
    return (f"requests={len(plan)} in_window={len(win)} "
            f"prompt_tokens={sum(len(p.prompt) for p in pool)} "
            f"answer_tokens={sum(p.max_new for p in pool)} "
            f"longest_prompt={max(len(p.prompt) for p in pool)} "
            f"longest_answer={max(p.max_new for p in pool)}")

