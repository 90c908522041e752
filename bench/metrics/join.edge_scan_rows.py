"""Edge-array rows the D-tree's edge scans read, per answered request.

Source: `QueryStats.edge_scan_rows` of the window's answered requests: the
program's count of the rows of the edge arrays its `edge_pairs` scans read
in one execution (a predicate's group, or all E edges). None where the
program does not count them.
"""


def read(w):
    counts = [getattr(s, "edge_scan_rows", None) for s in w.stats]
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    return sum(counts) / len(counts)
