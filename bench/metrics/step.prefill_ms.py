"""Model step: milliseconds per admission in the window from the
prefill's dispatch through its device sync.

The engine's ``prefill`` phase seconds: the prefill program, the write
of its cache into the pages and the readback of the first token, the
admission's one sync with the device (a re-admission reads nothing back,
and its device time lands in the next decode step).  An admission
stalls every slot's next token, so this moves ``itl_p95_ms``.

A program that keeps no ``decode.dispatch`` phase predates the phases'
profiler spans and ends ``prefill`` before the sync: nothing is read
there."""

KEYS = ("prefill", "decode.dispatch")


def read(r):
    n = len(r.work.prefills)
    if not n or any(k not in r.phase_s for k in KEYS):
        return None
    return 1e3 * r.phase_s["prefill"] / n
