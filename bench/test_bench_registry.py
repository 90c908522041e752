"""A configuration, its generator, a traffic mix and a per-layer metric
added as files are found by name, with no code edited."""
import json
import shutil
import time

import pytest

from bench.control import RefSystem
from bench.harness import Window, load_metric, run_cell
from bench.traffic import BENCH, load_cell


def _root(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "generators").mkdir()
    shutil.copy(BENCH / "generators" / "uba.py",
                tmp_path / "bench" / "generators")
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


# a hand-made citation graph that keeps the "Type/<8-digit id>" labels
TINY = """
def generate(config):
    n = int(config["papers"])
    triples, literals = [], set()
    for i in range(n):
        paper = f"Paper/{i:08d}"
        title = f"Title{i}"
        triples += [(paper, "type", "Class/Paper"),
                    (paper, "author", f"Author/{i % 3:08d}"),
                    (paper, "title", title)]
        literals.add(title)
        if i:
            triples.append((paper, "cites", f"Paper/{(i - 1) // 2:08d}"))
    triples += [(f"Author/{j:08d}", "type", "Class/Author")
                for j in range(3)]
    return triples, literals, {"Paper": n, "Author": 3}
"""


def _with_generator(root, generator):
    """The root's `tiny` configuration, made by `bench/generators/tiny.py`
    under that root, named as `generator`."""
    (root / "bench" / "generators" / "tiny.py").write_text(TINY)
    config = {"name": "tiny", "generator": generator, "papers": 12,
              "guarantees": {"max_answer_rows": 65536,
                             "row_guard": 1048576}}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    return load_cell("tiny.mix", root=root)


def test_bench_registry_finds_generator_by_name(tmp_path):
    cell = _with_generator(_root(tmp_path), "tiny")
    assert cell.bench_dir == tmp_path / "bench"
    line = run_cell(cell, 2 ** 31 + 7, 1.0, False, RefSystem(),
                    t_start=time.perf_counter())
    assert line["correct"] is True
    assert line["checks"]["answers_compared"]["value"] >= 1


def test_bench_registry_names_a_missing_generator(tmp_path):
    cell = _with_generator(_root(tmp_path), "absent")
    with pytest.raises(FileNotFoundError,
                       match=r"generators/absent\.py does not exist"):
        run_cell(cell, 7, 1.0, False, RefSystem(),
                 t_start=time.perf_counter())
