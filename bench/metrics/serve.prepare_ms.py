"""Host time inside the program's `prepare` segments per request (ms).

Source: the program's tracer (host clock): canonical form and plan-cache
lookup of each request before it is batched.
"""


def read(w):
    prep = [sp.duration_s for sp in w.spans
            if sp.name == "prepare" and sp.parent is None]
    if not w.requests or not prep:
        return None
    return 1e3 * sum(prep) / len(w.requests)
