"""One run of one cell: data, pool, warm-up, the measured window, the
comparison with the reference, and the contract line.

The system under test comes in through a `System` (`bench.system`
wraps the program); a test passes a fake one, so everything here runs
without a chip.  Times are `time.perf_counter` seconds.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from itertools import islice

import numpy as np

from .drive import closed_loop, open_loop
from .graph import Graph, relabel
from .profile_reduce import read_xplane, reduce_trace
from .reference import Reference
from .traffic import (BENCH, Cell, check_generated, make_data,
                      poisson_dues, rng_for, shuffled_cycles,
                      template_stream, zipf_sequence)

# past the window's close, requests due in it are still served this long
DRAIN_S = 60.0


def log(msg: str) -> None:
    print(msg, flush=True)
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Window:
    """What a per-layer metric reader (`bench/metrics/<name>.py`) gets."""
    requests: list                   # drive.Request, measured ones
    executions: int                  # server executions in the window
    spans: list = field(default_factory=list)   # program spans in it
    compiles: int = 0                # programs lowered in the window
    memory_peak_bytes: int | None = None
    trace: dict | None = None        # profile_reduce.reduce_trace
    peaks: dict | None = None        # bench/peaks.json entry

    @property
    def stats(self) -> list:
        return [r.result.stats for r in self.requests
                if r.result is not None]


def _canonical(res, to_ref: np.ndarray) -> np.ndarray:
    """Distinct answer rows in the reference's node ids, one column per
    template node in template order."""
    rows = np.asarray(res.rows)[:, np.argsort(res.cols, kind="stable")]
    return np.unique(to_ref[rows], axis=0)


def _gc_timer(pauses: list, clock):
    """A `gc.callbacks` entry that appends each collection's seconds."""
    started = []

    def timer(phase, _info):
        if phase == "start":
            started[:] = [clock()]
        elif started:
            pauses.append(clock() - started[0])
    return timer


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def load_metric(name: str, bench_dir=BENCH):
    """The `read(window)` of `<bench_dir>/metrics/<name>.py`."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", bench_dir / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Prepared:
    """A cell made ready for its window: the served dataset, the pool and
    its program queries, the warmed server, and how to read answers."""
    cell: Cell
    pool: list                       # traffic.Entry, request order
    queries: list                    # the program's form of each
    server: object
    to_ref: np.ndarray               # program node id -> reference id
    excluded_s: float                # the benchmark's own data check,
                                     # graph, sampling and reference:
                                     # not set-up


def prepare(cell: Cell, seed: int, trace: bool, system) -> Prepared:
    """Data, pool and warm-up; every shape the window uses is run here."""
    clock = time.perf_counter
    recipe = cell.traffic["templates"]
    guarantees = cell.config["guarantees"]

    t = clock()
    triples, literals, counts = make_data(cell.config, cell.bench_dir)
    served, back = relabel(triples, counts, rng_for(seed, "relabel"))
    forward = {v: k for k, v in back.items()}
    dataset = system.load(served, literals)
    log(f"data: {cell.config['name']} ({cell.config['generator']}) "
        f"triples={len(served)} load_s={clock() - t}")
    t = clock()
    check_generated(triples, literals, counts)
    graph = Graph(triples, literals)
    ref = Reference(graph, max_rows=int(guarantees["max_answer_rows"]),
                    max_intermediate=int(guarantees["row_guard"]))
    labels = np.asarray([back.get(s, s) for s in
                         system.labels(dataset).tolist()])
    to_ref = np.searchsorted(graph.labels, labels)
    if not (graph.labels[np.minimum(to_ref, graph.num_nodes - 1)]
            == labels).all():
        raise RuntimeError("the program's labels differ from the data")
    pool = list(islice(template_stream(graph, ref, recipe, forward),
                       int(recipe["pool"])))
    excluded_s = clock() - t
    log(f"reference: {excluded_s} s for the benchmark's data check, "
        f"graph, template sampling and reference answers")
    queries = [system.query(dataset, e.served) for e in pool]
    server = system.server(dataset, trace, **cell.traffic.get("server", {}))
    # every pass but the last submits the pool as one batch; the last runs
    # one template at a time, which also times each one's service
    for _ in range(int(cell.traffic["warmup_passes"]) - 1):
        for f in [server.submit(q) for q in queries]:
            f.result()
    service_ms = []
    for q in queries:
        t = clock()
        server.submit(q).result()
        service_ms.append(round((clock() - t) * 1e3, 3))
    log(f"service ms by pool rank, last warm-up pass: {service_ms}")
    n_conn = sum(bool(e.sampled.connections) for e in pool)
    log(f"pool: {len(pool)} templates, {n_conn} with a connection edge, "
        f"answer rows {min(len(e.answer) for e in pool)}.."
        f"{max(len(e.answer) for e in pool)}")
    return Prepared(cell, pool, queries, server, to_ref, excluded_s)


def open_schedule(cell: Cell, seed: int, seconds: float, n_templates: int,
                  rate: float | None = None):
    """(template sequence, due offsets) of an open-loop window."""
    arrivals = cell.traffic["arrivals"]
    dues = poisson_dues(float(rate or arrivals["rate_qps"]), seconds,
                        rng_for(seed, "arrivals"))
    seq = zipf_sequence(len(dues), n_templates,
                        float(cell.traffic["popularity"]["zipf_s"]),
                        rng_for(seed, "popularity"))
    return seq, dues


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, system,
             t_start: float, age0: float = 0.0, peaks: dict | None = None
             ) -> dict:
    """The contract's result line for one run (a dict)."""
    clock = time.perf_counter
    p = prepare(cell, seed, trace, system)
    server, queries, pool, to_ref = p.server, p.queries, p.pool, p.to_ref
    arrivals = cell.traffic["arrivals"]
    if arrivals["kind"] == "open":
        seq, dues = open_schedule(cell, seed, seconds, len(pool))
    else:
        seq = shuffled_cycles(len(pool), rng_for(seed, "order"))

    # set-up's garbage is collected before the window, and the window's
    # collections are timed: a pause shows as a stall in the latencies
    gc.collect()
    pauses = []
    gc_timer = _gc_timer(pauses, clock)
    gc.callbacks.append(gc_timer)

    # ---- the measured window ------------------------------------------
    ex0 = system.executions(server)
    system.window_start()
    annotate = contextlib.nullcontext
    if trace:
        profile_dir = tempfile.mkdtemp(prefix="bench-profile-")
        annotate = system.start_profile(profile_dir)
    start = clock()
    setup_s = start - t_start + age0 - p.excluded_s
    close = start + seconds
    with annotate("bench.window"):
        if arrivals["kind"] == "open":
            reqs, late = open_loop(server, queries, seq, dues, start, close,
                                   DRAIN_S, annotate=annotate)
            work = None
        else:
            reqs, work = closed_loop(server, queries, seq, start, close,
                                     annotate=annotate)
            late = []
    end = clock()
    gc.callbacks.remove(gc_timer)
    if trace:
        system.stop_profile()
    compiles = system.compiles()
    executions = system.executions(server) - ex0
    spans = system.spans(server, start, end)
    memory_peak = system.memory_peak_bytes()
    if late:
        log(f"generator lateness: {len(late)} wake-ups, median "
            f"{statistics.median(late) * 1e3} ms, max {max(late) * 1e3} ms")
    if pauses:
        log(f"gc in the window: {len(pauses)} collections, "
            f"{sum(pauses) * 1e3} ms in all, longest {max(pauses) * 1e3} ms")
    waits = [r.sent - r.due for r in reqs if r.sent is not None]
    if waits:
        log(f"queueing before submit: median "
            f"{statistics.median(waits) * 1e3} ms, max {max(waits) * 1e3} ms")

    # ---- correctness -------------------------------------------------
    failed = truncated = wrong = compared = 0
    for r in reqs:
        if r.result is None:
            failed += 1
            continue
        if r.result.stats.truncated:
            truncated += 1
            continue
        got = _canonical(r.result, to_ref)
        want = pool[r.template].answer
        compared += 1
        wrong += not (got.shape == want.shape and bool((got == want).all()))
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "truncated_answers": {"value": truncated, "limit": 0},
        "wrong_answers": {"value": wrong, "limit": 0},
        "answers_compared": {"value": compared, "limit_at_least": 1},
    }
    correct = (failed == 0 and truncated == 0 and wrong == 0
               and compared >= 1)
    log(f"answers: {compared} compared with the reference over "
        f"{len({r.template for r in reqs})} templates, {wrong} wrong, "
        f"{truncated} truncated, {failed} failed, of {len(reqs)}")

    # ---- metrics -----------------------------------------------------
    lat = [(r.done if r.done is not None else end) - r.due for r in reqs]
    window = Window(requests=reqs, executions=executions, spans=spans,
                    compiles=compiles, memory_peak_bytes=memory_peak,
                    peaks=peaks)
    device = system.device_info()
    device["memory_peak_bytes"] = memory_peak
    line = {"correct": correct, "attempted": len(reqs),
            "failed": failed + truncated + wrong}
    metrics = {}
    breakdown = None
    if not trace:
        values = {
            "p50_ms": _percentile(lat, 50) * 1e3,
            "p95_ms": _percentile(lat, 95) * 1e3,
            "setup_s": setup_s,
        }
        if work is not None:
            values["qps"] = work / seconds
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"end-to-end metric {m['name']!r} is not "
                               f"measured for this traffic")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        files = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
        layers = json.loads(
            (cell.bench_dir / "layers.json").read_text())["layers"]
        planes = read_xplane(files[0])
        shutil.rmtree(profile_dir, ignore_errors=True)
        log("trace planes: " + "; ".join(
            f"{pl['name']} [" + ", ".join(f"{ln['name']}: {len(ln['events'])}"
                                         for ln in pl["lines"]) + "]"
            for pl in planes))
        window.trace = tr = reduce_trace(planes, layers)
        total = sum(tr["module_s"].values())
        share = 100 * tr["unmatched_s"] / total if total else 0.0
        log(f"trace: window {tr['window_s']} s, busy {tr['busy_s']} s, "
            f"module time {total} s, by layer {tr['layer_s']}, unmatched "
            f"{tr['unmatched_s']} s ({share} % of module time)")
        mapped = {m for mods in layers.values() for m in mods}
        log("unmatched modules: " + json.dumps(
            {m: t for m, t in sorted(tr["module_s"].items(),
                                      key=lambda kv: -kv[1])
             if m not in mapped}))
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        top = sorted(tr["module_s"].items(), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[k, v] for k, v in top],
                     "idle_gaps": [[k, v] for k, v in tr["gaps"][:10]]}
        for m in cell.per_layer:
            v = load_metric(m["name"], cell.bench_dir)(window)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for name, c in checks.items():
        lim = c.get("limit", c.get("limit_at_least"))
        rel = "<=" if "limit" in c else ">="
        print(f"check {name}: {c['value']} (limit {rel} {lim})",
              file=sys.stderr, flush=True)
    return line
