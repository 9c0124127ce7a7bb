"""Kernels: ``ssd_chunk`` (the chunked Mamba-2 scan of each prefill)
against its roofline.

The least time sums, over every prefill in the window, the larger of
its SSD operations over peak bf16 and its bytes over peak HBM bandwidth,
as the model family counts them for the prompt's exact length over all
its Mamba-2 layers (``ssd_prefill_work``: x, B, C and dt in, y and the
final state out, the chunk ladder's operations; not the decay matrices
an implementation materialises).  The time is the device time of the
trace's ``ssd_chunk`` operations in the window.  An admission's prefill
lies in the longest gaps between tokens, so this moves ``itl_p95_ms``.
Reads nothing where the family counts no SSD work."""
from bench import work


def read(r):
    t = r.trace.family_s.get("ssd_chunk", 0.0) if r.trace else 0.0
    if (t <= 0 or not r.work.prefills
            or not hasattr(r.model, "ssd_prefill_work")):
        return None
    least = sum(work.min_seconds(*r.model.ssd_prefill_work(r.dims, n),
                                 r.peaks)[0] for n in r.work.prefills)
    return 100.0 * least / t
