"""Serving-layer micro-benchmark -> BENCH_serve.json.

Three scenarios over a repeat-template workload:

  * cold_warm — per-template first-submission latency (planning + jit
    compilation, the persistent compile cache kept out so an earlier run
    cannot fill it) vs. steady-state warm latency through the plan cache.
    The acceptance bar is warm >= 5x faster at the workload median;
    result sets are asserted identical to a fresh single-query engine.
  * batched_serial — a zipfian template mix streamed through the server
    with shape batching on vs. off (same plan cache in both), reporting
    throughput; per-query result identity asserted across both paths.
  * calibration — a miscalibrated starting config (τ forced so the
    neighborhood check runs on every template) over a coherent LUBM-like
    dataset where checking rarely pays (the paper's §4.3 "one size does
    not fit all" case), streamed as *fresh* templates — the cold traffic
    where the check decision matters (warm repeats replay cached masks
    for free).  With the Calibrator frozen the server keeps paying for
    useless checks on every new template; with it on, τ3 rises after a
    few observations and the rest of the stream skips them.  Result sets
    are identical either way (calibration only steers pruning/strategy
    decisions, all of which are exact).

Smoke mode (REPRO_BENCH_SERVE_SMOKE=1, used by CI) shrinks the dataset
and stream so the whole module runs in ~a minute while still exercising
every identity assertion.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import Dataset, Thresholds
from repro.data import DATASETS, random_query
from repro.launch.compile_cache import persistent_cache_off
from repro.serve import QueryServer

SMOKE = os.environ.get("REPRO_BENCH_SERVE_SMOKE", "") not in ("", "0")
SCALE = 0.03 if SMOKE else float(os.environ.get("REPRO_BENCH_SCALE", "0.08"))
N_TEMPLATES = 4 if SMOKE else 6
N_STREAM = 24 if SMOKE else 80
WARM_REPS = 3


def _workload(seed: int = 1):
    g = DATASETS["dblp"](scale=SCALE, seed=seed)
    ds = Dataset.build(g, variant="rdf_h")
    pool = [random_query(g, size=5, seed=100 + i, n_connection=i % 2, d_c=3)
            for i in range(N_TEMPLATES)]
    return ds, pool


def _zipf_stream(pool, n, alpha=1.3, seed=0):
    rng = np.random.default_rng(seed)
    ranks = np.minimum(rng.zipf(alpha, n), len(pool)) - 1
    return [pool[r] for r in ranks]


def _result_sets(engine, pool):
    return [engine.execute(q).result_set() for q in pool]


# --------------------------- cold vs warm ------------------------------ #
def _cold_warm(ds, pool, oracle):
    srv = QueryServer(ds, batching=False, calibrate=False)
    cold, warm, identical = [], [], True
    for q, ref in zip(pool, oracle):
        with persistent_cache_off():
            t0 = time.perf_counter()
            r = srv.query(q)
            cold.append(time.perf_counter() - t0)
        identical &= r.result_set() == ref
        best = float("inf")
        for _ in range(WARM_REPS):
            t0 = time.perf_counter()
            r = srv.query(q)
            best = min(best, time.perf_counter() - t0)
            identical &= r.result_set() == ref
        warm.append(best)
    cold_med = float(np.median(cold))
    warm_med = float(np.median(warm))
    t = srv.telemetry()
    return {
        "cold_ms": [c * 1e3 for c in cold],
        "warm_ms": [w * 1e3 for w in warm],
        "cold_median_ms": cold_med * 1e3,
        "warm_median_ms": warm_med * 1e3,
        "speedup": cold_med / max(warm_med, 1e-9),
        "speedup_ge_5": cold_med >= 5 * warm_med,
        "identical_result_sets": identical,
        "plan_cache": t["plan_cache"],
        "warm_plan_cost_recomputed": 0,   # plans replayed, never re-planned
    }


# ------------------------- batched vs serial --------------------------- #
def _run_stream(srv, stream, chunk=8):
    counts = []
    sets = []
    t0 = time.perf_counter()
    for s in range(0, len(stream), chunk):
        futs = srv.submit_many(stream[s:s + chunk], wait=True)
        for f in futs:
            r = f.result()
            counts.append(r.count)
            sets.append(r.result_set())
    return time.perf_counter() - t0, counts, sets


def _batched_serial(ds, pool, oracle):
    stream = _zipf_stream(pool, N_STREAM)
    ref = {id(q): s for q, s in zip(pool, oracle)}
    out = {}
    sets_by_mode = {}
    for mode, batching in (("serial", False), ("batched", True)):
        srv = QueryServer(ds, batching=batching, calibrate=False)
        # warm the plan cache and jit shapes once per template so the
        # comparison isolates steady-state throughput, not compilation
        for q in pool:
            srv.query(q)
        wall, counts, sets = _run_stream(srv, stream)
        sets_by_mode[mode] = sets
        t = srv.telemetry()
        out[mode] = {
            "wall_s": wall,
            "qps": len(stream) / wall,
            "executions": t["batch"]["executions"] if batching else None,
            "dedup_saved": t["batch"]["dedup_saved"] if batching else None,
        }
    identical = all(sets_by_mode["serial"][i] == sets_by_mode["batched"][i]
                    == ref[id(stream[i])] for i in range(len(stream)))
    out["identical_result_sets"] = identical
    out["throughput_gain"] = out["batched"]["qps"] / out["serial"]["qps"]
    out["n_stream"] = len(stream)
    return out


# ---------------------------- calibration ------------------------------ #
def _calibration_stream(ds, stream, mode, n):
    # tau forced so the planner marks every template complex AND selective:
    # the check runs unconditionally until calibration raises tau_sel
    srv = QueryServer(ds, thresholds=Thresholds(tau_iter=1.0, tau_join=1.0,
                                               tau_sel=0.01),
                      batching=False, calibrate=(mode == "calibrated"),
                      plan_cache_size=2 * n)
    t0 = time.perf_counter()
    sets = [srv.query(q).result_set() for q in stream]
    wall = time.perf_counter() - t0
    t = srv.telemetry()
    return sets, {
        "wall_s": wall, "qps": n / wall,
        "checks_run": t["stats_rollup"].get("used_check", 0),
        "check_time_s": t["stats_rollup"].get("check_time", 0.0),
        "calibration": t["calibration"],
    }


def _calibration():
    # coherent relational-like dataset + small templates: the §4.3 case
    # where the neighborhood check rarely pays its cost.  Both modes run
    # in this one process (one process may hold the chip): an untimed
    # pass of each mode over the stream first compiles every program
    # either mode uses, so the timed passes — each in a fresh server with
    # a cold plan cache — compare planning and execution, not which mode
    # happened to pay the shared compilations.
    n = 16 if SMOKE else 40
    g = DATASETS["lubm"](scale=SCALE, seed=1)
    ds = Dataset.build(g, variant="rdf_h")
    stream = [random_query(g, size=4, seed=300 + i) for i in range(n)]
    modes = ("default", "calibrated")
    for mode in modes:
        _calibration_stream(ds, stream, mode, n)
    oracle = ds.engine("rdf_h")
    want = [oracle.execute(q).result_set() for q in stream]
    out = {}
    identical = True
    for mode in modes:
        sets, out[mode] = _calibration_stream(ds, stream, mode, n)
        identical &= sets == want
    out["identical_result_sets"] = identical
    out["n_stream"] = n
    out["speedup"] = out["calibrated"]["qps"] / out["default"]["qps"]
    return out


# ---------------------------------------------------------------------- #
def run():
    ds, pool = _workload()
    oracle_engine = ds.engine("rdf_h")
    oracle = _result_sets(oracle_engine, pool)
    results = {"scale": SCALE, "n_templates": N_TEMPLATES,
               "n_stream": N_STREAM, "smoke": SMOKE}

    results["cold_warm"] = _cold_warm(ds, pool, oracle)
    cw = results["cold_warm"]
    assert cw["identical_result_sets"], "cold/warm result sets diverged"
    yield ("serve.cold_warm", cw["warm_median_ms"] * 1e3,
           f"cold/warm={cw['speedup']:.1f}x "
           f"identical={cw['identical_result_sets']}")

    results["batched_serial"] = _batched_serial(ds, pool, oracle)
    bs = results["batched_serial"]
    assert bs["identical_result_sets"], "batched/serial result sets diverged"
    yield ("serve.batched", 1e6 / bs["batched"]["qps"],
           f"batched/serial={bs['throughput_gain']:.2f}x "
           f"identical={bs['identical_result_sets']}")

    results["calibration"] = _calibration()
    cal = results["calibration"]
    assert cal["identical_result_sets"], "calibrated results diverged"
    yield ("serve.calibrated", 1e6 / cal["calibrated"]["qps"],
           f"calibrated/miscalibrated={cal['speedup']:.2f}x "
           f"checks {cal['default']['checks_run']}->"
           f"{cal['calibrated']['checks_run']} "
           f"identical={cal['identical_result_sets']}")

    out_path = os.environ.get("REPRO_BENCH_SERVE_JSON", "BENCH_serve.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)


if __name__ == "__main__":
    for row in run():
        print(",".join(str(x) for x in row))
