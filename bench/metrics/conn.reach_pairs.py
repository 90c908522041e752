"""(node, reach set) pairs gathered for connection edges per request.

Source: `QueryStats.conn_reach_pairs` of the window's answered requests.
"""


def read(w):
    stats = w.stats
    if not stats:
        return None
    return sum(s.conn_reach_pairs for s in stats) / len(stats)
