"""Model step: host-clock milliseconds per paged decode step in the window
(the engine's ``decode`` phase, which ends when the step's tokens are on
the host).  Every served token waits for one, so this moves
``itl_p95_ms``."""


def read(r):
    n = len(r.work.decode_contexts)
    return 1e3 * r.phase_s["decode"] / n if n else None
