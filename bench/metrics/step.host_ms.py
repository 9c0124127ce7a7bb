"""Scheduler: host milliseconds per step in the window in which the
program waits for no device work of its own.

The sum of the engine's phase seconds ``admission`` (retiring, admission
checks, page growth of admitted prompts), ``grow``, ``eviction``,
``tables`` (the block tables' copy to the device), ``decode.dispatch``
(the decode step's inputs and call) and ``decode.emit`` (the tokens'
bookkeeping), over every ``step()`` of the window.  The device waits for
this work between decode steps, and every token waits for a decode
step, so this moves ``itl_p95_ms``.  Reads nothing where the program
keeps no such phases."""

KEYS = ("admission", "grow", "eviction", "tables", "decode.dispatch",
        "decode.emit")


def read(r):
    if not r.work.steps or any(k not in r.phase_s for k in KEYS):
        return None
    return 1e3 * sum(r.phase_s[k] for k in KEYS) / r.work.steps
