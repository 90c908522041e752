"""Observability: tracing spans, the metrics registry, and EXPLAIN.

Three contracts under test:

  * schema pins — the Chrome trace event shape, the metrics snapshot
    shape, and `telemetry()["metrics"]` are consumed by external
    tooling, so their key sets are asserted exactly;
  * zero-cost-when-off — the NULL_TRACER path allocates nothing and a
    traced server returns byte-identical results to an untraced one;
  * end-to-end attribution — a governed + batched + fault-injected run
    produces one trace per query whose spans (submit → prepare →
    execute → governor routing → engine joins) all carry that query's
    trace id, and every ServingError names the trace that explains it.
"""
import glob
import json
import time

import pytest

from repro.core import Thresholds, make_engine
from repro.core.engine import EngineConfig
from repro.data import random_graph, random_query
from repro.obs import (HISTOGRAM_FIELDS, MetricsRegistry, NULL_SPAN,
                       NULL_TRACER, Tracer, render_explain)
from repro.serve import (DegradationExhausted, GovernorConfig,
                         QueryServer)
from repro.testing import Fault, FaultInjector


# --------------------------- fixtures ---------------------------------- #
@pytest.fixture(scope="module")
def graph():
    return random_graph(n_nodes=80, n_edges=220, n_preds=3,
                        n_literals=20, seed=1)


@pytest.fixture(scope="module")
def pool(graph):
    return [random_query(graph, size=4, seed=40 + i, n_connection=i % 2,
                         d_c=2) for i in range(4)]


def _forcing_cfg():
    """Route joins through sort-merge and connections through reach so
    injected kernel faults actually land (as in test_chaos.py)."""
    return EngineConfig(check_policy="selective", d_check=2, impl="ref",
                        thresholds=Thresholds(nested_join_max=1),
                        join_impl="sorted", connection_impl="reach")


# ------------------------------ metrics -------------------------------- #
def test_metrics_counter_gauge_histogram_basics():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(3)
    assert m.counter("c").value == 4
    m.gauge("g").set(2.5)
    assert m.gauge("g").value == 2.5
    h = m.histogram("h")
    for v in (1.0, 2.0, 4.0, 0.0):
        h.observe(v)
    assert h.count == 4 and h.sum == 7.0
    assert h.min == 0.0 and h.max == 4.0
    assert h.zeros == 1


def test_histogram_percentile_within_bucket_resolution():
    from repro.obs.metrics import HISTOGRAM_BASE, Histogram
    h = Histogram()
    vals = [0.001 * (1 + i) for i in range(1000)]       # 1ms .. 1s
    for v in vals:
        h.observe(v)
    for q in (50, 90, 99):
        exact = vals[int(len(vals) * q / 100) - 1]
        est = h.percentile(q)
        assert exact / HISTOGRAM_BASE <= est <= exact * HISTOGRAM_BASE
    # clamped to the observed range, 0.0 when empty
    assert Histogram().percentile(99) == 0.0
    assert h.percentile(100) <= h.max


def test_metrics_snapshot_schema_pinned():
    m = MetricsRegistry()
    m.counter("a").inc()
    m.gauge("b").set(1.0)
    m.histogram("c").observe(0.5)
    snap = m.snapshot()
    assert sorted(snap) == ["counters", "gauges", "histograms"]
    assert snap["counters"] == {"a": 1}
    assert snap["gauges"] == {"b": 1.0}
    assert sorted(snap["histograms"]["c"]) == sorted(HISTOGRAM_FIELDS)
    json.dumps(snap)                     # JSON-serializable end to end


def test_metric_name_type_conflict_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(ValueError):
        m.histogram("x")
    with pytest.raises(ValueError):
        m.gauge("x")


# ------------------------------ tracer --------------------------------- #
def test_tracer_nesting_and_parent_links():
    tr = Tracer()
    tid = tr.start(kind="unit")
    with tr.segment("root", tid) as root:
        with tr.span("child", k=1) as child:
            with tr.span("grandchild") as gc:
                assert gc.parent is child
            assert child.parent is root
    trace = tr.finish(tid)
    assert trace is not None and trace.trace_id == tid
    assert [s.name for s in trace.spans] == ["root", "child",
                                             "grandchild"]
    assert trace.roots() == [trace.spans[0]]
    assert all(s.end is not None and s.end >= s.start
               for s in trace.spans)


def test_span_error_stamped_and_exception_propagates():
    tr = Tracer()
    tid = tr.start()
    with pytest.raises(RuntimeError):
        with tr.segment("seg", tid):
            with tr.span("inner"):
                raise RuntimeError("boom")
    trace = tr.finish(tid)
    inner, = [s for s in trace.spans if s.name == "inner"]
    assert inner.error == "RuntimeError"
    assert not tr._stack                 # stack unwound through the raise


def test_null_paths_return_shared_null_span():
    tr = Tracer()
    assert tr.segment("s", None) is NULL_SPAN
    assert tr.segment("s", "t999999") is NULL_SPAN   # unknown id
    assert tr.span("orphan") is NULL_SPAN            # no open segment
    assert NULL_TRACER.start() is None
    assert NULL_TRACER.segment("s", "t000001") is NULL_SPAN
    assert NULL_TRACER.span("s") is NULL_SPAN
    assert NULL_SPAN.set(a=1) is NULL_SPAN
    assert not NULL_SPAN.live


def test_trace_bounds_ring_buffer_and_span_cap():
    tr = Tracer(max_traces=2, max_spans_per_trace=3)
    for _ in range(4):
        tid = tr.start()
        with tr.segment("seg", tid):
            for _ in range(5):
                with tr.span("s"):
                    pass
        tr.finish(tid)
    assert len(tr.finished) == 2         # ring buffer keeps the newest
    assert all(len(t.spans) == 3 for t in tr.finished)
    assert tr.dropped_spans == 4 * 3     # 5 nested + 1 root, cap 3


def test_chrome_event_schema_pinned(tmp_path):
    tr = Tracer()
    tid = tr.start()
    with tr.segment("seg", tid, who="q"):
        with tr.span("inner", rows=7):
            pass
    tr.finish(tid)
    path = tmp_path / "trace.json"
    info = tr.export_chrome(path)
    assert info["traces"] == 1 and info["events"] == 3
    doc = json.loads(path.read_text())
    assert sorted(doc) == ["displayTimeUnit", "traceEvents"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(meta) == 1 and meta[0]["name"] == "thread_name"
    assert len(spans) == 2
    for ev in spans:                     # the pinned complete-event shape
        assert sorted(ev) == ["args", "dur", "name", "ph", "pid",
                              "tid", "ts"]
        assert ev["pid"] == 1 and ev["args"]["trace_id"] == tid
        assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0
    assert spans[1]["args"]["rows"] == 7


def test_null_tracer_overhead_is_negligible():
    """The disabled path is a constant method returning a shared
    singleton — no allocation, no clock read.  50k span entries must be
    far under any serving-visible cost (bound is ~100x headroom)."""
    t0 = time.perf_counter()
    for _ in range(50_000):
        with NULL_TRACER.span("x") as sp:
            if sp.live:                  # the guard callers use
                sp.set(rows=1)
    assert time.perf_counter() - t0 < 0.5


# --------------------------- serving e2e ------------------------------- #
def test_traced_and_untraced_servers_agree(graph, pool):
    srv_a = QueryServer(graph, impl="ref")
    srv_b = QueryServer(graph, impl="ref", tracer=Tracer())
    for q in pool:
        assert srv_a.query(q).result_set() == srv_b.query(q).result_set()
    assert len(srv_b.tracer.finished) == len(pool)
    assert len(NULL_TRACER.finished) == 0


def test_end_to_end_chaos_trace_export(graph, pool, tmp_path):
    """Governed + batched + fault-injected serving exports a Chrome
    trace where every query's spans — submit, prepare, execute or
    fanout, governor routing (ladder rungs under the injected fault),
    and the engine's per-join spans — share that query's trace id."""
    tr = Tracer()
    srv = QueryServer(graph, cfg=_forcing_cfg(), tracer=tr,
                      governor=GovernorConfig())
    stream = pool * 2
    with FaultInjector(Fault("kernel_dispatch", "raise", every=1)):
        futs = srv.submit_many(stream, wait=True)
    degraded = 0
    for f in futs:
        assert f.trace_id is not None
        if f.done() and f._error is None:
            degraded += bool(f.result().stats.degraded_steps)
    assert degraded, "persistent kernel fault should force the ladder"

    path = tmp_path / "chaos_trace.json"
    info = tr.export_chrome(path)
    assert info["traces"] == len(stream)
    doc = json.loads(path.read_text())
    by_tid: dict = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            by_tid.setdefault(ev["tid"], []).append(ev)
    assert len(by_tid) == len(stream)
    names_by_trace = {}
    for evs in by_tid.values():
        ids = {ev["args"]["trace_id"] for ev in evs}
        assert len(ids) == 1             # one query per thread lane
        names_by_trace[ids.pop()] = [ev["name"] for ev in evs]
    for tid_, names in names_by_trace.items():
        assert names[0] == "submit" and "prepare" in names
        assert "execute" in names or "fanout" in names
    all_names = {n for names in names_by_trace.values() for n in names}
    # governor + engine spans land inside the right query's trace
    assert {"breaker", "ladder", "rung", "join"} <= all_names


# ----------------------- the profiler's clock -------------------------- #
def _host_events(directory):
    """(name, start ns, end ns) of every event on the host planes of the
    one profile under `directory`."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_traced_server_spans_land_on_the_profiler_clock(graph, pool,
                                                        tmp_path):
    """Under jax.profiler every live span is an `rdfh.<name>` annotation
    on the host plane, nested as the spans are."""
    import jax
    q = next(q for q in pool if q.connections)
    srv = QueryServer(graph, cfg=_forcing_cfg(), tracer=Tracer())
    srv.query(q)                         # compile outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            res = srv.query(q)
    finally:
        jax.profiler.stop_trace()
    assert res.stats.join_strategies and res.stats.conn_strategies
    events = _host_events(tmp_path)
    (lo, hi), = [(s, e) for n, s, e in events if n == "bench.window"]
    spans: dict = {}
    for n, s, e in events:
        if n.startswith("rdfh."):
            spans.setdefault(n, []).append((s, e))
    assert {"rdfh.submit", "rdfh.prepare", "rdfh.execute", "rdfh.finish",
            "rdfh.edge_pairs", "rdfh.join", "rdfh.conn_edge",
            "rdfh.sync"} <= set(spans)
    assert all(lo <= s <= e <= hi for iv in spans.values() for s, e in iv)
    for s, e in spans["rdfh.join"] + spans["rdfh.edge_pairs"]:
        assert any(a <= s and e <= b for a, b in spans["rdfh.execute"])


def test_host_syncs_alike_traced_or_not_and_one_sync_span_each(graph, pool):
    """`QueryStats.host_syncs` does not depend on tracing, and a traced
    execution holds one `sync` span per counted read, also where a
    replay answers reads from its tape."""
    traced = QueryServer(graph, cfg=_forcing_cfg(), tracer=Tracer())
    plain = QueryServer(graph, cfg=_forcing_cfg())
    for q in pool * 3:                   # cold, recording, then replaying
        f = traced.submit(q)
        got = f.result().stats.host_syncs
        assert got == plain.query(q).stats.host_syncs > 0
        spans = traced.tracer.get(f.trace_id).spans
        syncs = [s for s in spans if s.name == "sync"]
        assert got == len(syncs)
        assert all(s.attrs["what"] for s in syncs)


def test_edge_scan_rows_alike_traced_or_not_and_sum_of_scan_spans(graph,
                                                                  pool):
    """`QueryStats.edge_scan_rows` is exactly the `scanned` attributes of
    the request's `edge_pairs` spans summed, traced or not."""
    traced = QueryServer(graph, cfg=_forcing_cfg(), tracer=Tracer())
    plain = QueryServer(graph, cfg=_forcing_cfg())
    for q in pool * 2:                   # cold, then warm replay
        f = traced.submit(q)
        got = f.result().stats.edge_scan_rows
        assert got == plain.query(q).stats.edge_scan_rows > 0
        scans = [s for s in traced.tracer.get(f.trace_id).spans
                 if s.name == "edge_pairs"]
        assert scans and got == sum(s.attrs["scanned"] for s in scans)
        # every predicate of this graph has a group under E rows
        assert all(0 < s.attrs["scanned"] < graph.num_edges for s in scans)


def test_untraced_server_opens_no_profiler_annotation(graph, pool,
                                                      monkeypatch):
    import jax.profiler

    def refuse(*args, **kwargs):
        raise AssertionError("TraceAnnotation constructed")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    srv = QueryServer(graph, cfg=_forcing_cfg())
    for q in pool:
        assert srv.query(q).stats.host_syncs > 0
    with pytest.raises(AssertionError):  # the patch is the one spans use
        QueryServer(graph, cfg=_forcing_cfg(),
                    tracer=Tracer()).query(pool[0])


def test_serving_errors_carry_trace_id_and_rung_history(graph, pool):
    """DegradationExhausted (and every ServingError) names the trace
    holding its attempts, and renders the per-rung failure history."""
    tr = Tracer()
    srv = QueryServer(graph, cfg=_forcing_cfg(), tracer=tr,
                      governor=GovernorConfig(max_rows=0))
    f = srv.submit(pool[0])
    srv.flush()
    with pytest.raises(DegradationExhausted) as ei:
        f.result()
    exc = ei.value
    assert exc.trace_id == f.trace_id
    assert f"[trace {f.trace_id}]" in str(exc)
    history = exc.attempt_history.splitlines()
    assert len(history) == len(exc.attempts) >= 2
    assert any("primary" in line for line in history)
    # the named trace really holds the rung attempts
    trace = tr.get(f.trace_id)
    assert trace is not None
    rungs = [s for s in trace.spans if s.name == "rung"]
    assert len(rungs) >= 1
    assert all(s.attrs.get("outcome") == "failed" for s in rungs)


def test_telemetry_metrics_and_latency_schema_pinned(graph, pool):
    srv = QueryServer(graph, impl="ref", governor=GovernorConfig())
    for f in srv.submit_many(pool * 2, wait=True):
        f.result()
    t = srv.telemetry()
    assert sorted(t["latency"]) == ["cold_p50", "cold_p99", "n_cold",
                                    "n_warm", "p50", "p99", "warm_p50",
                                    "warm_p99"]
    assert t["latency"]["n_cold"] + t["latency"]["n_warm"] == len(pool) * 2
    m = t["metrics"]
    assert sorted(m) == ["counters", "gauges", "histograms"]
    assert m["counters"]["queries_served"] == len(pool) * 2
    for name in ("latency_s", "latency_cold_s", "latency_warm_s",
                 "prepare_s", "result_rows", "batch_bucket_size"):
        assert sorted(m["histograms"][name]) == sorted(HISTOGRAM_FIELDS)
    for name in ("pending", "plan_cache_entries", "reach_cache_bytes"):
        assert name in m["gauges"]
    json.dumps(t["metrics"])


def test_slow_query_log_captures_explain(graph, pool):
    srv = QueryServer(graph, impl="ref", slow_query_s=0.0,
                      slow_log_max=3)
    for f in srv.submit_many(pool, wait=True):
        f.result()
    log = srv.slow_queries()
    assert len(log) == 3                 # bounded, newest retained
    for entry in log:
        assert sorted(entry) == ["explain", "fingerprint", "latency_s",
                                 "trace_id", "warm"]
        assert entry["explain"].startswith("EXPLAIN template ")
    assert srv.telemetry()["metrics"]["counters"]["slow_queries"] == \
        len(pool)


# ------------------------------ EXPLAIN -------------------------------- #
def test_explain_golden_three_join_template(graph):
    """EXPLAIN on a fixed 3-join template is deterministic: two fresh
    servers render byte-identical reports (modulo the wall-clock
    prepare_time header line), with the pinned section structure and
    the §4.3 τ comparisons."""
    q = random_query(graph, size=4, seed=41, n_connection=0)

    def rendered():
        srv = QueryServer(graph, impl="ref", calibrate=False)
        cold = srv.explain(q)            # pre-execution plan state
        assert "(unlearned — cold execution pending" in cold
        srv.query(q)
        return srv.explain(q)

    a, b = rendered(), rendered()
    strip = [ln for ln in a.splitlines() if "prepare_time" not in ln]
    assert strip == [ln for ln in b.splitlines()
                     if "prepare_time" not in ln]
    text = "\n".join(strip)
    assert text.startswith("EXPLAIN template ")
    for section in ("candidates (IDMap intervals):",
                    "check decision (§4.3):",
                    "components: ",
                    "join order (Selinger DP over per-tree tables):",
                    "connection edges:",
                    "learned join sequence"):
        assert section in text
    for term in ("complex/iterations", "complex/join_product",
                 "power/max_selectivity", "=> use_check"):
        assert term in text
    # the learned join sequence renders est vs observed per join
    assert "impl=" in text and "est=" in text and "rows=" in text


def test_explain_renders_without_thresholds_or_decision(graph):
    """Duck-typed renderer: a policy-forced plan (decision None) and a
    thresholds-free call both render without the τ block."""
    cfg = EngineConfig(check_policy="never", d_check=2, impl="ref")
    eng = make_engine(graph, "rdf_h", impl="ref")
    q = random_query(graph, size=3, seed=42, n_connection=0)
    pq = eng.prepare(q)
    text = render_explain(pq)            # no thresholds given
    assert "est_iterations=" in text     # raw decision inputs instead
    srv = QueryServer(graph, cfg=cfg)
    forced = srv.explain(q)
    assert "forced by check_policy" in forced
