"""Scheduler: host-clock milliseconds per admission in the window.

The engine's ``admission`` and ``prefill`` phase seconds are read as one
sum: its prefill span ends before the device finishes, and the sync
falls in ``admission``.  An admission stalls every slot's next token, so
this moves ``itl_p95_ms``."""


def read(r):
    n = len(r.work.prefills)
    if not n:
        return None
    return 1e3 * (r.phase_s["admission"] + r.phase_s["prefill"]) / n
