"""Device time of the D-tree and join programs per request (ms).

Source: the profiler trace; the modules `bench/layers.json` puts in the
"D-tree matching and joins" layer, over the requests of the window.
"""


def read(w):
    if w.trace is None or not w.requests:
        return None
    s = w.trace["layer_s"].get("D-tree matching and joins", 0.0)
    return 1e3 * s / len(w.requests)
