"""The load generators: open loop (arrivals on a schedule) and closed
loop (one client).  Both drive a server that has `submit(query)` and
futures with `result()`, as `repro.serve.QueryServer` does; one thread
does everything, as the server has none of its own.

A request's latency runs from the moment it was due (open loop) or sent
(closed loop) until `result()` has returned its rows on the host.  In
the open loop, every request due by the time the loop looks is submitted
before the first pending future is resolved, which flushes them all as
one batch; requests that come due meanwhile wait for the next turn.

`annotate(name)` returns a context manager around each step (the
harness passes `jax.profiler.TraceAnnotation`, so the profiler's trace
says what the host was doing in each idle gap of the device).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass


@dataclass
class Request:
    template: int            # index into the cell's templates
    due: float               # clock seconds
    sent: float | None = None
    done: float | None = None
    result: object = None    # the MatchResult
    error: BaseException | None = None

    @property
    def latency(self) -> float | None:
        return None if self.done is None else self.done - self.due


def _resolve(reqs, futures, now, annotate):
    with annotate("bench.result"):
        for r, f in zip(reqs, futures):
            try:
                r.result = f.result()
            except Exception as e:       # noqa: BLE001 - a failed request
                r.error = e
    t = now()
    for r in reqs:
        r.done = t


def open_loop(server, queries, sequence, dues, start: float,
              close: float, drain_s: float, now=time.perf_counter,
              sleep=time.sleep, annotate=contextlib.nullcontext
              ) -> tuple[list, list]:
    """Requests for `sequence[k]` due at `start + dues[k]`; those due
    before `close` are measured, and served up to `drain_s` past it.
    Returns (requests, seconds by which each idle wait overslept)."""
    reqs = [Request(int(t), start + float(d))
            for t, d in zip(sequence, dues) if start + d < close]
    late = []
    nxt = 0
    pending, futures = [], []
    while nxt < len(reqs) or pending:
        t = now()
        if t > close + drain_s:
            break
        if nxt < len(reqs) and reqs[nxt].due <= t:
            with annotate("bench.submit"):
                while nxt < len(reqs) and reqs[nxt].due <= t:
                    r = reqs[nxt]
                    r.sent = t
                    futures.append(server.submit(queries[r.template]))
                    pending.append(r)
                    nxt += 1
        if pending:
            with annotate("bench.flush"):
                _resolve(pending, futures, now, annotate)
            pending, futures = [], []
            continue
        wait = reqs[nxt].due - t
        if wait > 0:
            with annotate("bench.idle"):
                sleep(wait)
            late.append(max(now() - reqs[nxt].due, 0.0))
    return reqs, late


def closed_loop(server, queries, sequence, start: float, close: float,
                now=time.perf_counter, annotate=contextlib.nullcontext
                ) -> tuple[list, float]:
    """One client sends `queries[k]` for k in `sequence` (an iterable
    long enough for the window), each after the last has returned, from
    `start` until `close`; the request open at `close` runs to its end.
    Returns (requests, completed work in the window, counting the open
    request by the share of it inside the window)."""
    reqs = []
    t = start
    for k in sequence:
        if t >= close:
            break
        r = Request(int(k), t, sent=t)
        with annotate("bench.flush"):
            f = server.submit(queries[r.template])
            _resolve([r], [f], now, annotate)
        reqs.append(r)
        t = r.done
    work = 0.0
    for r in reqs:
        if r.done <= close:
            work += 1.0
        else:
            work += (close - r.sent) / (r.done - r.sent)
    return reqs, work
