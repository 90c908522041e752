"""From a profiler trace to device time per layer, busy time and gaps.

`read_xplane` turns the `.xplane.pb` that `jax.profiler` writes into
plain lists: planes of lines of (name, start ns, duration ns) events.
`reduce_trace` works on those lists only, so a test can feed it a
synthetic trace.

  - Device planes are those named "/device:...".  A program's run is an
    event on the "XLA Modules" line, named by its XLA module
    (`jit_<function>`, maybe with a "(<id>)" or ".<n>" suffix); an
    operation's run is an event on the "XLA Ops" line.
  - Busy time is the union of the operation intervals (of the module
    intervals where a plane has no op line) inside the window, averaged
    over the device planes.
  - A module maps to a layer by its name without the suffix, through
    `bench/layers.json`; module time that maps to no layer is reported
    as unmatched.
  - The window is the host annotation named `window` (the harness's
    "bench.window"); each idle gap of the first device inside it is
    named by the host annotation, other than the window's, that covers
    most of it.
"""
from __future__ import annotations

import re
from collections import defaultdict

_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")


def module_base(name: str) -> str:
    return _SUFFIX.sub("", name)


def read_xplane(path) -> list[dict]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(e.name, float(e.start_ns),
                                      float(e.duration_ns))
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_trace(planes: list[dict], layers: dict[str, list[str]],
                 window: str = "bench.window", n_gaps: int = 10) -> dict:
    """{window_s, busy_s, module_s, layer_s, unmatched_s, gaps}.

    `layers` maps a layer name to its module names.  `gaps` lists
    (host annotation, seconds) for the `n_gaps` longest idle gaps of the
    first device, longest first.  Raises ValueError without a window
    annotation or a device plane."""
    host = [p for p in planes if not p["name"].startswith("/device:")]
    annotations = [(n, s, s + d) for p in host for line in p["lines"]
                   for n, s, d in line["events"]]
    spans = [(s, e) for n, s, e in annotations if n == window]
    if not spans:
        raise ValueError(f"no {window!r} annotation in the trace")
    lo, hi = spans[0]
    devices = [p for p in planes if p["name"].startswith("/device:")
               and any(line["events"] for line in p["lines"])]
    if not devices:
        raise ValueError("no device plane with events in the trace")
    of_layer = {m: layer for layer, mods in layers.items() for m in mods}

    module_s = defaultdict(float)
    busy, first_busy = [], None
    for p in devices:
        by_name = {line["name"]: line["events"] for line in p["lines"]}
        mods = by_name.get("XLA Modules", [])
        ops = by_name.get("XLA Ops", mods)
        for n, s, d in mods:
            for a, b in _clip([(s, s + d)], lo, hi):
                module_s[module_base(n)] += (b - a) * 1e-9
        merged = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if first_busy is None:
            first_busy = merged

    layer_s = defaultdict(float)
    unmatched = 0.0
    for m, t in module_s.items():
        if m in of_layer:
            layer_s[of_layer[m]] += t
        else:
            unmatched += t

    gaps = []
    edge = lo
    for s, e in first_busy + [[hi, hi]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:n_gaps]:
        best, cover = "none", 0.0
        for n, s, e in annotations:
            if n == window:
                continue
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = n, c
        named.append((best, (b - a) * 1e-9))
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / len(busy),
            "module_s": dict(module_s),
            "layer_s": dict(layer_s),
            "unmatched_s": unmatched,
            "gaps": named}
