"""Device: share of the traced window in which no operation ran on the
chip (1 - busy union / window).  Idle time is time every slot's next
token waits for the host, so this moves ``itl_p95_ms``."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
