"""The joins' share of their HBM roofline (%).

Least time: the logical bytes of every join in the window at the chip's
peak HBM bandwidth (`bench/peaks.json`).  The bytes come from the
program's `join` spans: each input row read once and each output row
written once, at 8 bytes a row.  Eight bytes are two int32 columns, the
fewest any table of the join path holds (every one carries at least one
edge's two endpoints), so the count is a floor whatever implements the
join, and the share cannot be overstated by it.
Time: the device time of the "D-tree matching and joins" layer.
"""

ROW_BYTES = 8


def read(w):
    if w.trace is None or w.peaks is None:
        return None
    device_s = w.trace["layer_s"].get("D-tree matching and joins", 0.0)
    rows = sum(s.attrs.get("a_rows", 0) + s.attrs.get("b_rows", 0)
               + s.attrs.get("rows", 0) for s in w.spans if s.name == "join")
    if device_s <= 0 or not rows:
        return None
    least_s = rows * ROW_BYTES / w.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
