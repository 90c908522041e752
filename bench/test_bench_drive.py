"""The load generators against a fake server on a fake clock."""
import pytest

from bench.drive import closed_loop, open_loop


class Clock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def sleep(self, d):
        self.t += d


class Server:
    """Serves each pending request in `service` seconds of the clock,
    all pending ones in one flush."""

    def __init__(self, clock, service):
        self.clock, self.service = clock, service
        self.pending, self.flushes = [], []

    def submit(self, q):
        f = Future(self, q)
        self.pending.append(f)
        return f

    def flush(self):
        batch, self.pending = self.pending, []
        self.flushes.append(len(batch))
        for f in batch:
            self.clock.t += self.service
            f.value = ("rows of", f.q)


class Future:
    def __init__(self, server, q):
        self.server, self.q, self.value = server, q, None

    def result(self):
        if self.value is None:
            self.server.flush()
        return self.value


def test_bench_drive_open_loop_times_from_due():
    clock = Clock()
    server = Server(clock, 0.2)
    reqs, late = open_loop(server, ["a", "b"], [0, 1, 0, 1],
                           [0.0, 0.1, 0.15, 1.0], start=0.0, close=2.0,
                           drain_s=1.0, now=clock.now, sleep=clock.sleep)
    # r0 alone; r1 and r2 came due while it ran and share one flush;
    # r3 after an idle wait
    assert server.flushes == [1, 2, 1]
    assert [r.latency for r in reqs] == pytest.approx([0.2, 0.5, 0.45, 0.2])
    assert [r.sent - r.due for r in reqs] == pytest.approx(
        [0.0, 0.1, 0.05, 0.0])
    assert late == [0.0]
    assert reqs[1].result == ("rows of", "b")


def test_bench_drive_open_loop_drops_requests_due_after_close():
    clock = Clock()
    reqs, _ = open_loop(Server(clock, 0.01), ["a"], [0, 0, 0],
                        [0.0, 0.5, 1.5], start=0.0, close=1.0, drain_s=1.0,
                        now=clock.now, sleep=clock.sleep)
    assert len(reqs) == 2


def test_bench_drive_open_loop_gives_up_after_drain():
    clock = Clock()
    reqs, _ = open_loop(Server(clock, 5.0), ["a"], [0, 0],
                        [0.0, 0.5], start=0.0, close=1.0, drain_s=1.0,
                        now=clock.now, sleep=clock.sleep)
    assert reqs[0].done == pytest.approx(5.0)
    assert reqs[1].done is None and reqs[1].result is None


def test_bench_drive_closed_loop_counts_the_open_request_by_share():
    clock = Clock()
    server = Server(clock, 0.3)
    reqs, work = closed_loop(server, ["a", "b"], iter([1, 0] * 5),
                             start=0.0, close=1.0, now=clock.now)
    assert [r.sent for r in reqs] == pytest.approx([0.0, 0.3, 0.6, 0.9])
    assert [r.template for r in reqs] == [1, 0, 1, 0]
    assert reqs[0].result == ("rows of", "b")
    assert work == pytest.approx(3 + 0.1 / 0.3)
