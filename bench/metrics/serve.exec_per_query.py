"""Server executions per request completed in the window.

Source: the server's telemetry (`batch.executions`, read before and
after the window).  Below 1 where the shape batcher answers concurrent
repeats of one template with one execution.
"""


def read(w):
    done = sum(r.result is not None for r in w.requests)
    return w.executions / done if done else None
