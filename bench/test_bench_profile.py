"""The trace reduction on a small synthetic trace."""
import pytest

from bench.profile_reduce import module_base, reduce_trace

NS = 1e-9


def _planes():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.window", 1000, 10000),
        ("bench.flush", 1000, 5000),
        ("bench.idle", 6000, 5000)]}]}
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_sort_probe_expand(12)", 1500, 1000),
            ("jit__gather_count.3", 3000, 500),
            ("jit_other", 4000, 200),
            # straddles the window's start: only 500 ns count
            ("jit_sort_probe_expand(12)", 500, 1000)]},
        {"name": "XLA Ops", "events": [
            ("fusion", 1500, 600), ("sort", 2000, 500),
            ("gather", 3000, 500), ("copy", 4000, 200),
            ("early", 500, 1000)]}]}
    return [host, device]


LAYERS = {"joins": ["jit_sort_probe_expand"], "check": ["jit__gather_count"]}


def test_bench_profile_busy_layers_and_unmatched():
    r = reduce_trace(_planes(), LAYERS)
    assert r["window_s"] == pytest.approx(10000 * NS)
    # [1000,1500) + [1500,2500) + [3000,3500) + [4000,4200)
    assert r["busy_s"] == pytest.approx(2200 * NS)
    assert r["layer_s"]["joins"] == pytest.approx(1500 * NS)
    assert r["layer_s"]["check"] == pytest.approx(500 * NS)
    assert r["unmatched_s"] == pytest.approx(200 * NS)


def test_bench_profile_gaps_named_by_host_annotation():
    r = reduce_trace(_planes(), LAYERS)
    names = [g[0] for g in r["gaps"]]
    assert r["gaps"][0][1] == pytest.approx(6800 * NS)
    assert names[0] == "bench.idle"          # [4200, 11000)
    assert sorted(names[1:]) == ["bench.flush", "bench.flush"]


def test_bench_profile_module_suffixes():
    assert module_base("jit_sort_probe(123)") == "jit_sort_probe"
    assert module_base("jit__gather_count.7") == "jit__gather_count"
    assert module_base("jit_plain") == "jit_plain"


def test_bench_profile_needs_window_and_device():
    host, device = _planes()
    with pytest.raises(ValueError):
        reduce_trace([device], LAYERS)
    with pytest.raises(ValueError):
        reduce_trace([host], LAYERS)
