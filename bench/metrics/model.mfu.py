"""Whole serving step: model operations done in the window over the
window times the chip's peak bf16 rate.

The operations are those the model needs for the prompt tokens
prefilled and the tokens decoded in the window: every layer's matmuls,
causal attention over the live context, and the logits that are read
(``bench/work.py``).  Work recomputed after an eviction counts once.
Moves ``itl_p95_ms``, as the kernels' rooflines do."""
from bench import work


def read(r):
    flops = sum(work.prefill_flops(r.dims, n) for n in r.work.prefills)
    flops += sum(work.decode_flops(r.dims, c)
                 for c in r.work.decode_contexts)
    if flops <= 0:
        return None
    return 100.0 * flops / (r.window_s * r.peaks["bf16_flops_per_s"])
