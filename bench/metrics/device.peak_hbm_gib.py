"""Device: peak bytes in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GiB.  Memory left over
bounds slots and pool pages, so this moves ``output_tokens_per_s``."""


def read(r):
    if r.memory_peak_bytes is None:
        return None
    return r.memory_peak_bytes / 2 ** 30
