"""The program tracer's cost, and where an execution's time goes: one
set-up of an open-loop cell, then windows of the cell's traffic with the
program's tracer off and on in turn, then one window under the profiler.

    python3 bench/exec_split.py --workload lubm.lookup-zipf --seed 5 --seconds 51 --pairs 3

Every window replays the same schedule.  Each off and on window prints
its requests, p50 from the due time and failed requests: the two kinds
compare the cost of the spans, their profiler annotations and the
`sync` spans, with no profile being taken.  The profiled window prints
its own p50, the device's busy share of the window, the program's
per-layer span metrics, the request of median wall time split into its
segments, and the first device's idle seconds inside the program's
`execute` spans by innermost program span (`bench.span_reduce`), on a
line that starts "idle inside execute by span:".
"""
import argparse
import contextlib
import gc
import glob
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

SPAN_METRICS = ("serve.host_syncs", "serve.sync_wait_ms", "serve.prepare_ms",
                "serve.exec_host_ms", "conn.host_ms")


def _split(trace) -> dict:
    """Milliseconds of one trace: each root segment, and the `sync` spans
    and `edge_pairs` spans inside them."""
    out = {"wall": trace.wall_s * 1e3}
    for s in trace.spans:
        if s.parent is None or s.name in ("sync", "edge_pairs"):
            out[s.name] = out.get(s.name, 0.0) + s.duration_s * 1e3
    out["syncs"] = sum(s.name == "sync" for s in trace.spans)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=3,
                    help="tracer-off and tracer-on windows, in turn")
    args = ap.parse_args()
    from bench.run import boot
    from bench.traffic import load_cell
    cell = load_cell(args.workload)
    boot(cell.chips)
    import numpy as np
    from bench.drive import open_loop
    from bench.harness import (BENCH, DRAIN_S, Window, load_metric, log,
                               open_schedule, prepare)
    from bench.profile_reduce import read_xplane, reduce_trace
    from bench.span_reduce import idle_in_execute
    from bench.system import Program
    from repro.obs.trace import NULL_TRACER

    system = Program(cell.chips)
    p = prepare(cell, args.seed, True, system)
    server, tracer = p.server, p.server.tracer
    seq, dues = open_schedule(cell, args.seed, args.seconds, len(p.pool))

    def window(name, annotate=contextlib.nullcontext):
        gc.collect()
        start = time.perf_counter()
        with annotate("bench.window"):
            reqs, _ = open_loop(server, p.queries, seq, dues, start,
                                start + args.seconds, DRAIN_S,
                                annotate=annotate)
        end = time.perf_counter()
        lat = [((r.done or end) - r.due) * 1e3 for r in reqs]
        log(f"tracer {name}: requests={len(reqs)} "
            f"p50_ms={np.percentile(lat, 50)} "
            f"failed={sum(r.result is None for r in reqs)}")
        return reqs, start, end

    for _ in range(args.pairs):
        for name, t in (("off", NULL_TRACER), ("on", tracer)):
            server.tracer = server.engine.tracer = t
            window(name)

    server.tracer = server.engine.tracer = tracer
    profile_dir = tempfile.mkdtemp(prefix="bench-profile-")
    annotate = system.start_profile(profile_dir)
    reqs, start, end = window("on, profiled", annotate)
    system.stop_profile()
    planes = read_xplane(glob.glob(f"{profile_dir}/**/*.xplane.pb",
                                   recursive=True)[0])
    shutil.rmtree(profile_dir, ignore_errors=True)

    layers = json.loads((BENCH / "layers.json").read_text())["layers"]
    tr = reduce_trace(planes, layers)
    log(f"trace: window {tr['window_s']} s, busy {tr['busy_s']} s, "
        f"module time {sum(tr['module_s'].values())} s")
    w = Window(requests=reqs, executions=0,
               spans=system.spans(server, start, end))
    for name in SPAN_METRICS:
        log(f"{name}: {load_metric(name)(w)}")
    log(f"sync spans per request: "
        f"{sum(sp.name == 'sync' for sp in w.spans) / len(reqs)}")
    traces = [t for t in tracer.finished if start <= t.created <= end]
    if traces:
        walls = sorted(traces, key=lambda t: t.wall_s)
        log("median request, ms: "
            + json.dumps(_split(walls[len(walls) // 2])))
        log("median of each part over requests, ms: " + json.dumps(
            {k: statistics.median(_split(t).get(k, 0.0) for t in traces)
             for k in ("wall", "submit", "prepare", "execute", "finish",
                       "sync", "edge_pairs", "syncs")}))
    split = idle_in_execute(planes)
    execute_s, idle_s = split["execute_s"], split["idle_s"]
    log(f"execute {execute_s} s, idle inside execute {idle_s} s "
        f"({100 * idle_s / execute_s if execute_s else 0.0} %)")
    log("idle inside execute by span: " + json.dumps(
        dict(sorted(split["by_span"].items(), key=lambda kv: -kv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
