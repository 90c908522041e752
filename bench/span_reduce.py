"""Device idle time inside the program's `execute` spans, by program span.

Works on the planes of `profile_reduce.read_xplane`, so a test can feed
it a synthetic trace.

  - The program's spans are host annotations named "rdfh.<span>"
    (`repro.obs.trace` opens one for every live span); a request's
    execution is an "rdfh.execute" annotation.
  - Busy time is the first device's, as `reduce_trace` takes it: the
    union of its operation intervals (of its module intervals where it
    has no op line).
  - Idle time inside execute is the part of the "rdfh.execute"
    intervals inside the window annotation where that device is not
    busy.  Each idle piece goes to the innermost "rdfh.*" annotation
    covering it: the one that started last.  Inside an execute interval
    that is at least "rdfh.execute" itself, so the per-span seconds sum
    to the idle time.
"""
from __future__ import annotations

import heapq

from .profile_reduce import _clip, _union

PREFIX = "rdfh."
EXECUTE = PREFIX + "execute"


def _innermost(spans):
    """Disjoint (start, end, name) pieces of the time covered by `spans`
    ((name, start, end) each), each named by the covering span that
    started last."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    active, out, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            n, s, e = by_start[i]
            heapq.heappush(active, (-s, e, n))
            i += 1
        # the top started last; one that has ended is dropped when it
        # surfaces, so the top left covers [a, b)
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            out.append((a, b, active[0][2]))
    return out


def idle_in_execute(planes: list[dict], window: str = "bench.window"
                    ) -> dict:
    """{execute_s, idle_s, by_span}: seconds of "rdfh.execute" inside the
    window, the first device's idle seconds inside them, and those idle
    seconds by innermost program span.  Raises ValueError without a
    window annotation or a device plane with events."""
    host = [p for p in planes if not p["name"].startswith("/device:")]
    annotations = [(n, s, s + d) for p in host for line in p["lines"]
                   for n, s, d in line["events"]]
    bounds = [(s, e) for n, s, e in annotations if n == window]
    if not bounds:
        raise ValueError(f"no {window!r} annotation in the trace")
    lo, hi = bounds[0]
    devices = [p for p in planes if p["name"].startswith("/device:")
               and any(line["events"] for line in p["lines"])]
    if not devices:
        raise ValueError("no device plane with events in the trace")
    by_name = {line["name"]: line["events"] for line in devices[0]["lines"]}
    ops = by_name.get("XLA Ops", by_name.get("XLA Modules", []))
    busy = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))

    execute = _union(_clip([(s, e) for n, s, e in annotations
                            if n == EXECUTE], lo, hi))
    idle, j = [], 0
    for a, b in execute:
        edge = a
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            if busy[k][0] > edge:
                idle.append((edge, busy[k][0]))
            edge = max(edge, busy[k][1])
            k += 1
        if edge < b:
            idle.append((edge, b))

    named = _innermost([(n, s, e) for n, s, e in annotations
                        if n.startswith(PREFIX)])
    by_span: dict[str, float] = {}
    i = 0
    for a, b in idle:
        while i < len(named) and named[i][1] <= a:
            i += 1
        k = i
        while k < len(named) and named[k][0] < b:
            s, e, n = named[k]
            cover = min(b, e) - max(a, s)
            if cover > 0:
                by_span[n] = by_span.get(n, 0.0) + cover * 1e-9
            k += 1
    return {"execute_s": sum(e - s for s, e in execute) * 1e-9,
            "idle_s": sum(e - s for s, e in idle) * 1e-9,
            "by_span": by_span}
