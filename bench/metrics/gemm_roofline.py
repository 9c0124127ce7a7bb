"""Kernels: the dense matmuls (``gemm`` family) against their roofline.

The least time sums, over every matmul of every step in the window (the
model family's ``step_gemms``) at its real rows (live slots in decode,
the prompt in prefill, one row of logits per prefill), the larger of its
operations over peak bf16 and its bytes (A, B and C once) over peak HBM
bandwidth.  The time is the device
time of the trace's ``gemm`` rows, which include XLA's slicing of each
layer's weights out of the stacked parameters, where the weights are
read from HBM.  Every gap between tokens holds a decode step's matmuls,
and the longest also an admission's prefill, so this moves
``itl_p95_ms``."""
from bench import work


def read(r):
    t = r.trace.family_s.get("gemm", 0.0) if r.trace else 0.0
    if t <= 0:
        return None
    shapes = []
    for contexts in r.work.decode_contexts:
        shapes += r.model.step_gemms(r.dims, len(contexts), len(contexts))
    for length in r.work.prefills:
        shapes += r.model.step_gemms(r.dims, length, 1)
    if not shapes:
        return None
    return 100.0 * work.gemm_min_seconds(shapes, r.peaks) / t
