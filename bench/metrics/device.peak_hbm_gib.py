"""Peak device memory of the run (GiB).

Source: `memory_stats()["peak_bytes_in_use"]` after the window, the
fullest chip.
"""


def read(w):
    if w.memory_peak_bytes is None:
        return None
    return w.memory_peak_bytes / 2 ** 30
