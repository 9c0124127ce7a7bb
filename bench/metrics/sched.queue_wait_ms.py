"""Scheduler: milliseconds a request waits in the queue per admission
in the window.

The engine's ``queue_wait`` counter: from ``submit()``, or from the
eviction for a re-admission, to the start of the admission, summed over
admissions.  A request's first token waits for its admission, so this
moves ``ttft_p90_ms``.  Chat only: the backlog's queue grows all through
its window, so there the wait restates the backlog's length.  Reads
nothing where the program keeps no such counter."""


def read(r):
    n = len(r.work.prefills)
    if not n or "queue_wait" not in r.phase_s:
        return None
    return 1e3 * r.phase_s["queue_wait"] / n
