"""Device-to-host reads per answered request.

Source: `QueryStats.host_syncs` of the window's answered requests: the
program's count of `repro.obs.trace.host_read` calls in one execution.
None where the program does not count them.
"""


def read(w):
    counts = [getattr(s, "host_syncs", None) for s in w.stats]
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    return sum(counts) / len(counts)
