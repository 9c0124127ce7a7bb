"""Kernels: ``flash_decode`` (paged decode attention) against its roofline.

The least time is, per decode step and layer, the larger of the
operations over peak bf16 and the bytes over peak HBM bandwidth, where
the bytes are the live KV of every live slot plus its query and output
(``bench/work.py``); the time is the device time of the trace's
``flash_decode`` operations in the window.  Every gap between tokens
holds one decode step, so this moves ``itl_p95_ms``."""
from bench import work


def read(r):
    t = r.trace.family_s.get("flash_decode", 0.0) if r.trace else 0.0
    if t <= 0 or not r.work.decode_contexts:
        return None
    least = 0.0
    for contexts in r.work.decode_contexts:
        flops, nbytes = work.decode_attention(r.dims, contexts)
        least += work.min_seconds(flops, nbytes, r.peaks)[0]
    return 100.0 * least * r.dims["num_hidden_layers"] / t
