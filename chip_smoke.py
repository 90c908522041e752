"""Smoke run of the RDF-h serving path on one TPU chip.

Drives the path a user calls, once, in this one process:

    lubm graph from --seed -> Dataset.build -> QueryServer (impl="auto",
    so every join kernel resolves to its compiled Pallas form) -> a pool
    of random_query templates submitted cold, then again warm, then cold
    and warm on a fresh server under
    jax.transfer_guard_device_to_host("disallow")

and checks every answer against an Engine over the same Dataset with
impl="ref" (the kernels' pure-jnp twins).  The governor is off, so no
degradation ladder can hide a kernel failure: a failed query raises out
of `.result()` and the script exits non-zero.

    python chip_smoke.py [--scale 8.0] [--seed 0]

Earlier lines report the device, graph size, build/cold/warm seconds,
join and connection strategy totals and device memory.  The last line of
standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``,
printed only when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
# a cold pass is mostly TPU compilation (each pow2 capacity class of a join
# is a program, its sorts alone 10-20 s to compile at 64k+ rows), so the
# pool stays small enough for a cold run to end in minutes
N_TEMPLATES = 8


def _log(msg: str) -> None:
    print(msg, flush=True)


def _canonical(res):
    """Distinct result rows, columns in query-node order, sorted: the
    array form of `MatchResult.result_set`."""
    import numpy as np
    rows = np.asarray(res.rows)[:, np.argsort(res.cols, kind="stable")]
    return np.unique(rows, axis=0)


def template_pool(graph, n: int, seed: int):
    """Sizes 5 and 6; every fourth template turns one edge into a
    connection edge so the connection-edge joins run too.  Nearly a third
    of the nodes keep their exact label, so the pool mixes anchored
    templates (a few rows) with type-level ones whose intermediate joins
    reach the engine's row limit."""
    from repro.data import random_query
    return [random_query(graph, size=5 + i % 2, seed=seed * 1000 + i,
                         n_connection=int(i % 4 == 3), d_c=3,
                         exact_nodes=0.3)
            for i in range(n)]


def _serve(server, pool):
    """Submit the whole pool, resolve every future; seconds until every
    result's rows are on the host, plus the results."""
    t0 = time.perf_counter()
    futures = server.submit_many(pool)
    results = [f.result() for f in futures]
    rows = [_canonical(r) for r in results]
    return time.perf_counter() - t0, results, rows


def smoke(scale: float, seed: int, n_templates: int) -> None:
    """Every phase; raises on the first failure."""
    import jax
    from repro.core import Dataset
    from repro.data import DATASETS
    from repro.serve import QueryServer

    t0 = time.perf_counter()
    graph = DATASETS["lubm"](scale=scale, seed=seed)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ds = Dataset.build(graph, variant="rdf_h")
    build_s = time.perf_counter() - t0
    _log(f"graph: lubm scale={scale} seed={seed} triples={graph.num_edges} "
         f"nodes={graph.num_nodes} generate_s={gen_s} build_s={build_s}")

    pool = template_pool(graph, n_templates, seed)
    n_conn = sum(bool(q.connections) for q in pool)
    _log(f"pool: {len(pool)} templates, {n_conn} with a connection edge")

    # calibration off: the thresholds stay those the ref engine plans
    # with, so both run the same plan, also where the row limit cuts it
    server = QueryServer(ds, calibrate=False)
    cold_s, cold, cold_rows = _serve(server, pool)
    _log(f"cold_s={cold_s}")
    warm_s, warm, warm_rows = _serve(server, pool)
    _log(f"warm_s={warm_s}")

    # the served pass once more, cold then warm on a fresh server, with
    # every implicit device-to-host read refused: on a TPU each read the
    # serving path makes has to be repro.obs.trace.host_read's explicit
    # one (CPU arrays are host memory, so there the guard never fires)
    guarded = QueryServer(ds, calibrate=False)
    with jax.transfer_guard_device_to_host("disallow"):
        for phase in ("cold", "warm"):
            guard_s, _, guard_rows = _serve(guarded, pool)
            for i, (got, want) in enumerate(zip(guard_rows, cold_rows)):
                if got.shape != want.shape or (got != want).any():
                    raise RuntimeError(f"template {i} guarded {phase}: "
                                       f"{len(got)} rows, cold pass "
                                       f"{len(want)}")
            _log(f"guarded_{phase}_s={guard_s}: no implicit "
                 f"device-to-host read")

    t = server.telemetry()
    if t["query_errors"] or t["queries_shed"]:
        raise RuntimeError(f"query errors {t['query_errors']}, "
                           f"shed {t['queries_shed']}")
    joins, conns = Counter(), Counter()
    for i, res in enumerate(cold + warm):
        if res.stats.degraded_steps:
            raise RuntimeError(f"degraded steps {res.stats.degraded_steps}")
        joins.update(res.stats.join_strategies)
        conns.update(res.stats.conn_strategies)
        if i < len(pool):
            _log(f"template {i}: rows={res.count} "
                 f"truncated={res.stats.truncated} "
                 f"joins={res.stats.join_strategies} "
                 f"conns={res.stats.conn_strategies}")
    _log(f"join_strategies={dict(sorted(joins.items()))} "
         f"conn_strategies={dict(sorted(conns.items()))} "
         f"truncated={sum(r.stats.truncated for r in cold + warm)}")
    if not joins:
        raise RuntimeError("no join ran: the pool does not reach the kernels")
    if not conns:
        raise RuntimeError("no connection edge ran")

    ref = ds.engine("rdf_h", impl="ref")
    t0 = time.perf_counter()
    ref_res = [ref.execute(q) for q in pool]
    want = [_canonical(r) for r in ref_res]
    _log(f"ref_s={time.perf_counter() - t0}")
    for i, (r, w) in enumerate(zip(ref_res, want)):
        # random_query samples a subgraph, so a complete answer holds at
        # least that match; a row-limited one may have lost it
        if len(w) == 0 and not r.stats.truncated:
            raise RuntimeError(f"template {i}: the sampled match is missing")
        for phase, res, got in (("cold", cold[i], cold_rows[i]),
                                ("warm", warm[i], warm_rows[i])):
            if res.stats.truncated != r.stats.truncated:
                raise RuntimeError(f"template {i} {phase}: truncated="
                                   f"{res.stats.truncated}, ref engine "
                                   f"{r.stats.truncated}")
            if got.shape != w.shape or (got != w).any():
                raise RuntimeError(f"template {i} {phase}: {len(got)} rows, "
                                   f"ref engine {len(w)}")
    _log(f"result sets identical to the impl=ref engine: {len(pool)} "
         f"templates, cold and warm, {sum(len(w) for w in want)} rows")

    mem = jax.devices()[0].memory_stats() or {}
    _log(f"device memory: bytes_in_use={mem.get('bytes_in_use')} "
         f"peak_bytes_in_use={mem.get('peak_bytes_in_use')}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=8.0,
                    help="lubm generator scale (1.0 ~ 75k triples)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    _log(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)} compile_cache={cache}")
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: backend is {jax.default_backend()!r}, not a TPU",
              file=sys.stderr)
        return 1
    smoke(args.scale, args.seed, N_TEMPLATES)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
