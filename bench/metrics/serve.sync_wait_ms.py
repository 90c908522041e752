"""Host time inside the program's `sync` spans per request (ms).

Source: the program's tracer (host clock): a `sync` span times one
device-to-host read, the host waiting on the device plus the copy.
None where the program has no such spans.
"""


def read(w):
    syncs = [sp.duration_s for sp in w.spans if sp.name == "sync"]
    if not w.requests or not syncs:
        return None
    return 1e3 * sum(syncs) / len(w.requests)
