"""A configuration, a traffic mix and a per-layer metric added as files
are found by name, with no code edited."""
import json
import shutil
import time

from bench.control import RefSystem
from bench.harness import Window, load_metric, run_cell
from bench.traffic import BENCH, load_cell


def _root(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    config = json.loads((BENCH / "configs" / "lubm.json").read_text())
    config.update(name="tiny", universities=1)
    config["profile"]["departments_per_university"] = [1, 1]
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(config))
    mix = json.loads((BENCH / "traffic" / "lookup-zipf.json").read_text())
    mix["templates"]["pool"] = 3
    mix["arrivals"]["rate_qps"] = 20.0
    (tmp_path / "bench" / "traffic" / "mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "x.requests.py").write_text(
        "def read(w):\n    return len(w.requests)\n")
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny",
                       "traffic": "mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "x.requests", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "Serving",
             "moves": "p50_ms", "workloads": ["tiny.mix"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_bench_registry_finds_new_files_by_name(tmp_path):
    root = _root(tmp_path)
    cell = load_cell("tiny.mix", root=root)
    assert cell.config["universities"] == 1
    assert cell.traffic["templates"]["pool"] == 3
    assert [m["name"] for m in cell.per_layer] == ["x.requests"]
    read = load_metric("x.requests", root / "bench")
    assert read(Window(requests=[1, 2], executions=2)) == 2

    line = run_cell(cell, 2 ** 31 + 5, 1.0, False, RefSystem(),
                    t_start=time.perf_counter())
    assert line["correct"] is True
    assert set(line["metrics"]) == {"p50_ms", "setup_s"}
    shutil.rmtree(root / "bench")
