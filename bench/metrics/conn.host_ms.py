"""Host time inside the program's `conn_edge` spans per request (ms).

Source: the program's tracer (host clock): the reach gather and the
breadth-first search of connection edges run on the host.
"""


def read(w):
    if not w.requests:
        return None
    s = sum(sp.duration_s for sp in w.spans if sp.name == "conn_edge")
    return 1e3 * s / len(w.requests)
