"""Kernels: ``grouped_gemm`` (the held experts' SwiGLU GEMMs) against
its roofline.

The least time is, for decode and for prefill each, the larger of two
times: the operations of every row routed to a held expert (gate, up and
down) over peak bf16, and the bytes over peak HBM bandwidth, which are
each hit expert's three matrices once and each row's inputs and outputs
once (the model family's ``expert_work``).  Rows and experts hit are the
engine's counters ``expert_rows.<regime>`` and ``experts_hit.<regime>``,
summed over MoE layers and steps in the window.  Taking the larger of
the two sums per regime, not per step, gives a lower bound of the least
time.  The time is the device time of the trace's ``grouped_gemm``
operations in the window.  Every gap between tokens holds a decode
step's expert GEMMs, and the longest also an admission's prefill, so
this moves ``itl_p95_ms``.  Reads nothing where the program keeps no
such counters or the family counts no expert work."""
from bench import work

REGIMES = ("decode", "prefill")


def read(r):
    t = r.trace.family_s.get("grouped_gemm", 0.0) if r.trace else 0.0
    keys = [f"{c}.{g}" for g in REGIMES for c in ("expert_rows",
                                                  "experts_hit")]
    if (t <= 0 or not hasattr(r.model, "expert_work")
            or any(k not in r.phase_s for k in keys)):
        return None
    least = sum(work.min_seconds(*r.model.expert_work(
        r.dims, r.phase_s["expert_rows." + g], r.phase_s["experts_hit." + g]),
        r.peaks)[0] for g in REGIMES)
    return 100.0 * least / t if least > 0 else None
