"""Device idle time inside `execute` by program span, on a synthetic
trace, and the readers of the program's span and sync metrics."""
from types import SimpleNamespace

import pytest

from bench.harness import Window, load_metric
from bench.span_reduce import idle_in_execute

NS = 1e-9


def _planes():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.window", 0, 1000),
        ("bench.flush", 50, 900),            # the benchmark's: not a span
        ("rdfh.execute", 100, 500),          # [100, 600)
        ("rdfh.join", 200, 200),             # [200, 400)
        ("rdfh.sync", 300, 50),              # [300, 350)
        ("rdfh.finish", 600, 50),            # outside execute
        ("rdfh.execute", 700, 400)]}]}       # [700, 1100): the window cuts
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_a", 150, 700)]},
        {"name": "XLA Ops", "events": [
            ("fusion", 150, 100), ("sort", 320, 20), ("gather", 450, 350)]}]}
    other = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [("fusion", 0, 1000)]}]}
    return [host, device, other]


def test_span_reduce_idle_goes_to_the_innermost_span():
    r = idle_in_execute(_planes())
    assert r["execute_s"] == pytest.approx(800 * NS)
    # idle [100,150) [250,320) [340,450) [800,1000); the second device
    # is not read
    assert r["idle_s"] == pytest.approx(430 * NS)
    assert r["by_span"] == pytest.approx({
        "rdfh.execute": (50 + 50 + 200) * NS,     # under execute alone
        "rdfh.join": (50 + 50) * NS,              # [250,300) [350,400)
        "rdfh.sync": (20 + 10) * NS})             # [300,320) [340,350)
    assert sum(r["by_span"].values()) == pytest.approx(r["idle_s"])


def test_span_reduce_without_program_spans_or_ops_line():
    host, device, _ = _planes()
    host = {"name": host["name"], "lines": [{"name": "python", "events": [
        ("bench.window", 0, 1000)]}]}
    modules_only = {"name": device["name"], "lines": device["lines"][:1]}
    r = idle_in_execute([host, modules_only])
    assert r == {"execute_s": 0.0, "idle_s": 0.0, "by_span": {}}


def test_span_reduce_needs_window_and_device():
    host, device, _ = _planes()
    with pytest.raises(ValueError):
        idle_in_execute([device])
    with pytest.raises(ValueError):
        idle_in_execute([host])


def _span(name, ms, parent=None):
    return SimpleNamespace(name=name, duration_s=ms * 1e-3, parent=parent)


def _window(stats, spans):
    reqs = [SimpleNamespace(result=SimpleNamespace(stats=s)) for s in stats]
    return Window(requests=reqs, executions=len(reqs), spans=spans)


def test_span_metrics_read_a_window():
    ex1, ex2 = _span("execute", 10.0), _span("execute", 6.0)
    join = _span("join", 4.0, ex1)
    spans = [_span("submit", 0.5), _span("prepare", 1.0), ex1, join,
             _span("sync", 2.0, join), _span("sync", 1.0, ex1),
             _span("prepare", 3.0), ex2, _span("sync", 3.0, ex2),
             _span("finish", 0.5)]
    w = _window([SimpleNamespace(host_syncs=2),
                 SimpleNamespace(host_syncs=1)], spans)
    assert load_metric("serve.host_syncs")(w) == pytest.approx(1.5)
    assert load_metric("serve.sync_wait_ms")(w) == pytest.approx(3.0)
    assert load_metric("serve.prepare_ms")(w) == pytest.approx(2.0)
    assert load_metric("serve.exec_host_ms")(w) == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["serve.host_syncs", "serve.sync_wait_ms",
                                  "serve.prepare_ms", "serve.exec_host_ms"])
def test_span_metrics_none_without_data(name):
    assert load_metric(name)(Window(requests=[], executions=0)) is None
    # a program with spans but no host-sync count or `sync` spans: all
    # but the prepare time have nothing to read
    old = _window([SimpleNamespace(cache_hit=True)],
                  [_span("prepare", 1.0), _span("execute", 5.0)])
    got = load_metric(name)(old)
    assert (got is None) == (name != "serve.prepare_ms")
