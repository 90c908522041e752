"""Host time of `execute` segments outside their `sync` spans per
request (ms): the host's own work between dispatches.

Source: the program's tracer (host clock).  None where the program has
no `sync` spans, since the split needs them.
"""


def _root(span):
    while span.parent is not None:
        span = span.parent
    return span


def read(w):
    execute = [sp for sp in w.spans
               if sp.name == "execute" and sp.parent is None]
    syncs = [sp for sp in w.spans if sp.name == "sync"]
    if not w.requests or not execute or not syncs:
        return None
    inside = sum(sp.duration_s for sp in syncs
                 if _root(sp).name == "execute")
    return 1e3 * (sum(sp.duration_s for sp in execute) - inside) \
        / len(w.requests)
