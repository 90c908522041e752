"""Serving subsystem: plan cache, fingerprints, batching, calibration.

The core guarantee is *identity*: every serving path — cold vs. warm plan
cache, batched vs. one-at-a-time, calibrated vs. default thresholds —
returns byte-identical result sets to a fresh single-query engine run.
"""
import numpy as np
import pytest

import repro.core.engine as engine_mod
from repro.core import (make_engine, brute_force_match, Thresholds,
                        CostModel, JoinEstimator, ReplayEstimator,
                        QueryStats, ReachCache)
from repro.core.query import QueryTemplate, QueryEdge, ConnectionEdge
from repro.data import random_graph, random_query
from repro.serve import (QueryServer, PlanCache, ShapeBatcher, Calibrator,
                         template_fingerprint, prepare_cached, dataset_key)


# --------------------------- fixtures ---------------------------------- #
@pytest.fixture(scope="module")
def graph():
    return random_graph(n_nodes=120, n_edges=360, n_preds=4,
                        n_literals=30, seed=3)


@pytest.fixture(scope="module")
def pool(graph):
    return [random_query(graph, size=4, seed=10 + i, n_connection=i % 2,
                         d_c=2) for i in range(4)]


def _fresh_results(graph, queries):
    eng = make_engine(graph, "rdf_h", impl="ref")
    return [eng.execute(q).result_set() for q in queries]


def _permute(query, perm):
    """Renumber a template's nodes: original node i becomes perm[i]."""
    inv = {}
    for i, p in enumerate(perm):
        inv[p] = i
    kws = [query.keywords[inv[j]] for j in range(len(perm))]
    return QueryTemplate(
        keywords=kws,
        edges=[QueryEdge(perm[e.src], perm[e.dst], e.pred)
               for e in query.edges],
        connections=[ConnectionEdge(perm[c.src], perm[c.dst], c.max_dist,
                                    c.bidirectional)
                     for c in query.connections])


# ----------------------- canonical fingerprints ------------------------ #
def test_fingerprint_invariant_under_renumbering(graph, pool):
    rng = np.random.default_rng(0)
    for q in pool:
        fp = template_fingerprint(q)
        for _ in range(4):
            perm = rng.permutation(q.num_nodes).tolist()
            assert template_fingerprint(_permute(q, perm)) == fp


def test_fingerprint_distinguishes_templates(graph, pool):
    fps = {template_fingerprint(q) for q in pool}
    assert len(fps) == len(pool)


def test_fingerprint_distinguishes_edge_direction():
    a = QueryTemplate(keywords=["X/", "Y/"], edges=[QueryEdge(0, 1, 2)])
    b = QueryTemplate(keywords=["X/", "Y/"], edges=[QueryEdge(1, 0, 2)])
    assert template_fingerprint(a) != template_fingerprint(b)


def test_fingerprint_bidirectional_connection_symmetric():
    """A bidirectional connection is a symmetric constraint: swapping its
    endpoints must not change the fingerprint (a directed one must)."""
    a = QueryTemplate(keywords=["X/", "Y/"],
                      connections=[ConnectionEdge(0, 1, 3, True)])
    b = QueryTemplate(keywords=["X/", "Y/"],
                      connections=[ConnectionEdge(1, 0, 3, True)])
    assert template_fingerprint(a) == template_fingerprint(b)
    da = QueryTemplate(keywords=["X/", "Y/"],
                       connections=[ConnectionEdge(0, 1, 3, False)])
    db = QueryTemplate(keywords=["X/", "Y/"],
                       connections=[ConnectionEdge(1, 0, 3, False)])
    assert template_fingerprint(da) != template_fingerprint(db)


def test_canonicalize_degenerate_symmetric_template_is_fast():
    """Fully symmetric templates (n! automorphisms) must not blow up the
    individualization search — the branch budget degrades it to greedy,
    which stays deterministic for a given numbering."""
    import time
    n = 10
    q = QueryTemplate(keywords=["A/"] * n)
    t0 = time.perf_counter()
    fp = template_fingerprint(q)
    assert time.perf_counter() - t0 < 2.0
    assert template_fingerprint(q) == fp          # deterministic


def test_canonicalize_symmetric_template_stable():
    """Fully symmetric templates (automorphic nodes) still canonicalize
    identically from any input numbering."""
    base = QueryTemplate(keywords=["A/", "A/", "B/"],
                         edges=[QueryEdge(0, 2, 1), QueryEdge(1, 2, 1)])
    fp = template_fingerprint(base)
    for perm in ([1, 0, 2], [2, 1, 0], [0, 2, 1]):
        assert template_fingerprint(_permute(base, perm)) == fp


def test_permuted_template_hits_cache_and_remaps(graph, pool):
    q = pool[1]
    srv = QueryServer(graph, impl="ref")
    assert srv.query(q).result_set() == _fresh_results(graph, [q])[0]
    perm = list(reversed(range(q.num_nodes)))
    qp = _permute(q, perm)
    # the permuted template shares the cache entry but its result set is
    # expressed in ITS node numbering — compare against a fresh run of qp
    assert srv.query(qp).result_set() == _fresh_results(graph, [qp])[0]
    pc = srv.telemetry()["plan_cache"]
    assert pc["hits"] >= 1 and pc["entries"] == 1


# --------------------------- plan cache -------------------------------- #
def test_plan_cache_lru_eviction():
    cache = PlanCache(max_entries=2)

    class _PQ:
        version = 0
    a, b, c = _PQ(), _PQ(), _PQ()
    cache.put("d", "a", a)
    cache.put("d", "b", b)
    assert cache.get("d", "a") is a       # touch a -> b is now LRU
    cache.put("d", "c", c)
    assert len(cache) == 2 and cache.evictions == 1
    assert cache.get("d", "b") is None    # evicted
    assert cache.get("d", "a") is a and cache.get("d", "c") is c


def test_prepare_cached_revalidates_on_version_change(graph, pool):
    eng = make_engine(graph, "rdf_h", impl="ref")
    cache = PlanCache()
    did = dataset_key(graph)
    q = pool[0]
    pq1, _, hit1 = prepare_cached(eng, q, cache, did, version=0)
    eng.execute_prepared(pq1)             # learn the execution state
    assert not hit1 and pq1.executions == 1
    pq2, _, hit2 = prepare_cached(eng, q, cache, did, version=1)
    assert hit2 and pq2 is pq1
    assert pq2.version == 1               # revalidated in place
    assert cache.revalidations == 1
    # unchanged decision -> learned state survived
    assert pq2.executions == 1


def test_revalidate_flip_resets_learned_state(graph, pool):
    eng = make_engine(graph, "rdf_h", impl="ref")
    eng.cfg.thresholds = Thresholds(tau_iter=0.0, tau_join=0.0,
                                    tau_sel=0.0)   # force check ON
    q = pool[0]
    pq = eng.prepare(q)
    assert pq.use_check
    eng.execute_prepared(pq)
    assert pq.masks is not None and pq.executions == 1
    eng.cfg.thresholds = Thresholds(tau_iter=1e18, tau_join=1e18,
                                    tau_sel=1e18)  # force check OFF
    kept = eng.revalidate(pq, version=1)
    assert not kept and not pq.use_check
    assert pq.masks is None and pq.executions == 0 and pq.join_seq == []
    # and the reset plan still executes correctly
    assert eng.execute_prepared(pq).result_set() == \
        _fresh_results(graph, [q])[0]


def test_reach_cache_lru_bound():
    rc = ReachCache(max_entries=3)
    for i in range(5):
        rc.put_array(i, 1, 1, np.asarray([i], np.int32))
    assert len(rc) == 3 and rc.evictions == 2
    assert rc.get_array(0, 1, 1) is None
    assert rc.get_array(4, 1, 1) is not None


# ---------------------- serving identity grid --------------------------- #
@pytest.mark.parametrize("batching", [False, True])
@pytest.mark.parametrize("calibrate", [False, True])
def test_serving_identity_grid(graph, pool, batching, calibrate):
    """cold pass + warm pass x {batched, serial} x {calibrated, default}:
    result sets byte-identical to a fresh single-query engine."""
    want = _fresh_results(graph, pool)
    srv = QueryServer(graph, impl="ref", batching=batching,
                      calibrate=calibrate)
    stream = pool + pool[::-1] + pool     # repeats in varied order
    refs = want + want[::-1] + want
    futs = srv.submit_many(stream, wait=True)
    for f, ref in zip(futs, refs):
        assert f.result().result_set() == ref
    t = srv.telemetry()
    assert t["plan_cache"]["entries"] == len(pool)
    assert t["plan_cache"]["hits"] >= len(pool)      # repeats hit
    assert t["queries_served"] == len(stream)


def test_warm_execution_skips_planning_and_check(graph, pool, monkeypatch):
    """A warm plan-cache execution never re-enters plan_table_joins /
    plan_connections / decide, and replays cached candidate masks."""
    q = pool[1]                           # has a connection edge
    srv = QueryServer(graph, impl="ref", calibrate=False)
    r_cold = srv.query(q)
    assert not r_cold.stats.cache_hit

    def _boom(*a, **k):
        raise AssertionError("planning re-entered on warm execution")
    monkeypatch.setattr(engine_mod, "plan_table_joins", _boom)
    monkeypatch.setattr(engine_mod, "plan_connections", _boom)
    monkeypatch.setattr(engine_mod, "decide", _boom)
    monkeypatch.setattr(engine_mod, "check_interval_candidates", _boom)
    # warm replays must not re-enter the connection cost model either
    monkeypatch.setattr(engine_mod, "connection_selectivity", _boom)
    monkeypatch.setattr(engine_mod, "endpoint_reach", _boom)
    monkeypatch.setattr(engine_mod, "choose_connection_impl", _boom)
    r_warm = srv.query(q)
    assert r_warm.stats.cache_hit
    assert r_warm.stats.join_retries == 0
    assert r_warm.result_set() == r_cold.result_set()


def test_calibrated_thresholds_never_change_results(graph, pool):
    """Drive the calibrator hard (miscalibrated start) — results must
    stay identical to the default engine on every query."""
    want = _fresh_results(graph, pool)
    srv = QueryServer(graph, impl="ref", calibrate=True,
                      thresholds=Thresholds(tau_iter=0.1, tau_join=0.1,
                                            tau_sel=0.01))
    for _ in range(3):
        for q, ref in zip(pool, want):
            assert srv.query(q).result_set() == ref
    assert srv.calibrator.observed > 0


# ----------------------------- batching -------------------------------- #
def test_shape_batcher_dedups_identical_fingerprints():
    batcher = ShapeBatcher()
    calls = []

    def execute(item):
        calls.append(item)
        return f"r{item}"
    batcher.add(1, "fpA", 64)
    batcher.add(2, "fpA", 64)
    batcher.add(3, "fpB", 64)
    out = dict(batcher.flush(execute))
    assert len(calls) == 2                # one execution per fingerprint
    assert out == {1: "r1", 2: "r1", 3: "r3"}
    t = batcher.telemetry
    assert t.queries == 3 and t.executions == 2 and t.dedup_saved == 1


def test_batched_dedup_still_remaps_columns(graph, pool):
    """Two renumberings of one template submitted in one batch share one
    execution but each future gets its own column mapping."""
    q = pool[1]
    perm = list(reversed(range(q.num_nodes)))
    qp = _permute(q, perm)
    srv = QueryServer(graph, impl="ref", batching=True)
    f1, f2 = srv.submit_many([q, qp], wait=True)
    assert f1.result().result_set() == _fresh_results(graph, [q])[0]
    assert f2.result().result_set() == _fresh_results(graph, [qp])[0]
    assert srv.batcher.telemetry.executions == 1
    assert srv.batcher.telemetry.dedup_saved == 1


def test_failed_bucket_does_not_orphan_other_futures(graph, pool,
                                                     monkeypatch):
    """An execution error resolves only its own futures with the error;
    the rest of the flush still completes."""
    srv = QueryServer(graph, impl="ref", batching=False)
    boom = RuntimeError("engine exploded")
    real = srv.engine.execute_prepared

    def flaky(pq):
        if pq.fingerprint == template_fingerprint(pool[0]):
            raise boom
        return real(pq)
    monkeypatch.setattr(srv.engine, "execute_prepared", flaky)
    f_bad, f_ok = srv.submit_many([pool[0], pool[1]], wait=True)
    assert f_bad.done() and f_ok.done()
    with pytest.raises(RuntimeError, match="engine exploded"):
        f_bad.result()
    assert f_ok.result().result_set() == _fresh_results(graph, [pool[1]])[0]
    assert srv.query_errors == 1
    assert srv.telemetry()["query_errors"] == 1


def test_warm_replay_pins_connection_strategy(graph, pool):
    """The per-edge reach/cross choice recorded by the cold run is
    replayed warm even if the live cost model has moved since, so the
    join-size replay cannot desync."""
    q = pool[1]                           # has a connection edge
    srv = QueryServer(graph, impl="ref", calibrate=False)
    r_cold = srv.query(q)
    assert sum(r_cold.stats.conn_strategies.values()) >= 1
    # shove the cost model to extremes that would flip any auto choice
    srv.engine.cfg.cost_model.reach_scale = 1e9
    srv.engine.cfg.cost_model.cross_scale = 1e-9
    r_warm = srv.query(q)
    assert r_warm.stats.cache_hit
    assert r_warm.stats.conn_strategies == r_cold.stats.conn_strategies
    assert r_warm.stats.join_retries == 0
    assert r_warm.result_set() == r_cold.result_set()


def test_result_future_lazy_flush(graph, pool):
    srv = QueryServer(graph, impl="ref")
    f = srv.submit(pool[0])
    assert not f.done()
    res = f.result()                      # triggers the flush
    assert f.done() and f.latency is not None
    assert res.result_set() == _fresh_results(graph, [pool[0]])[0]


# ---------------------------- calibrator ------------------------------- #
def _mk_stats(**kw):
    qs = QueryStats()
    for k, v in kw.items():
        setattr(qs, k, v)
    return qs


def test_calibrator_join_bias_direction():
    th, cm = Thresholds(), CostModel()
    cal = Calibrator(th, cm, alpha=1.0)
    # estimates 10x too high -> scale shrinks below 1
    cal.observe(_mk_stats(n_estimated_joins=2,
                          join_est_log_bias=2 * np.log(10.0)))
    assert cm.join_est_scale < 1.0
    # estimates 10x too low -> scale grows above 1 (and is clipped)
    for _ in range(20):
        cal.observe(_mk_stats(n_estimated_joins=1,
                              join_est_log_bias=-np.log(1000.0)))
    assert 1.0 < cm.join_est_scale <= Calibrator.SCALE_BOUND


def test_calibrator_tau_sel_separates_observed_selectivities():
    from repro.core.planner import PlanDecision

    def plan(sel):
        return PlanDecision(use_check=True, complex_query=True,
                            max_selectivity=sel, est_iterations=1e6,
                            est_join_product=1e12)
    th, cm = Thresholds(tau_sel=0.01), CostModel()
    cal = Calibrator(th, cm)
    # selectivity 4.0 failed to prune -> tau_sel jumps past it
    cal.observe(_mk_stats(used_check=True, candidates_before=100,
                          candidates_after=99, plan=plan(4.0)))
    assert th.tau_sel > 4.0
    assert cal.version == 1
    # selectivity 12.0 pruned hard -> tau_sel drops below it
    cal.observe(_mk_stats(used_check=True, candidates_before=100,
                          candidates_after=10, plan=plan(12.0)))
    assert 4.0 < th.tau_sel < 12.0
    # warm repeats are not new evidence
    v = cal.version
    cal.observe(_mk_stats(used_check=True, cache_hit=True,
                          candidates_before=100, candidates_after=99,
                          plan=plan(4.0)))
    assert cal.version == v


def test_calibrator_ignores_warm_observations_entirely():
    """Warm replays are the cold run's observation over again — no EWMA
    may move on them (a hot template would dominate by repetition)."""
    th, cm = Thresholds(), CostModel()
    cal = Calibrator(th, cm, alpha=1.0)
    cal.observe(_mk_stats(cache_hit=True, n_estimated_joins=2,
                          join_est_log_bias=5.0, conn_est_pairs=100.0,
                          conn_connected_pairs=1, conn_reach_pairs=5,
                          conn_est_reach_pairs=500.0))
    assert (cm.join_est_scale, cm.conn_sel_scale, cm.reach_scale) \
        == (1.0, 1.0, 1.0)
    assert cal.version == 0


def test_cross_impl_edges_do_not_accrue_conn_predictions(graph, pool):
    """The cross path never measures connected/reach pairs, so it must
    not contribute predictions either — otherwise every cross edge looks
    like 'predicted N, observed 0' and poisons conn_sel_scale."""
    q = pool[1]                           # has a connection edge
    eng = make_engine(graph, "rdf_h", impl="ref")
    eng.cfg.connection_impl = "cross"
    qs = eng.execute(q).stats
    assert sum(qs.conn_strategies.values()) >= 1
    assert qs.conn_est_pairs == 0.0
    assert qs.conn_est_reach_pairs == 0.0
    eng2 = make_engine(graph, "rdf_h", impl="ref")
    eng2.cfg.connection_impl = "reach"
    qs2 = eng2.execute(q).stats
    assert qs2.conn_est_pairs > 0.0


def test_calibrator_join_scale_converges_to_full_correction():
    """The recorded bias is measured on already-scaled estimates; the
    calibrator must divide the applied scale back out, or a raw c-fold
    over-estimate converges to 1/sqrt(c) instead of 1/c."""
    th, cm = Thresholds(), CostModel()
    cal = Calibrator(th, cm, alpha=1.0)
    c = 4.0                               # raw model over-estimates 4x
    for _ in range(10):
        # bias as the engine would record it: raw bias + applied scale
        bias = np.log(c) + np.log(cm.join_est_scale)
        cal.observe(_mk_stats(n_estimated_joins=1, join_est_log_bias=bias))
    assert np.isclose(cm.join_est_scale, 1.0 / c, rtol=1e-6)


def test_calibrator_ignores_policy_forced_checks():
    """check_policy='always' runs the check with no decide() decision
    (plan=None): no τ evidence, no version bump."""
    th, cm = Thresholds(), CostModel()
    cal = Calibrator(th, cm)
    cal.observe(_mk_stats(used_check=True, plan=None,
                          candidates_before=100, candidates_after=100))
    assert th.tau_sel == Thresholds().tau_sel and cal.version == 0


def test_server_does_not_mutate_caller_thresholds(graph, pool):
    th = Thresholds(tau_iter=0.1, tau_join=0.1, tau_sel=0.01)
    srv = QueryServer(graph, impl="ref", calibrate=True, thresholds=th)
    for _ in range(2):
        for q in pool:
            srv.query(q)
    assert (th.tau_iter, th.tau_join, th.tau_sel) == (0.1, 0.1, 0.01)
    assert srv.calibrator.thresholds is not th


def test_dataset_key_is_content_based():
    ga = random_graph(n_nodes=60, n_edges=150, seed=1)
    gb = random_graph(n_nodes=60, n_edges=150, seed=2)   # same shape
    assert dataset_key(ga) != dataset_key(gb)
    assert dataset_key(ga) == dataset_key(ga)


def test_server_rejects_cfg_plus_thresholds(graph):
    from repro.core import EngineConfig
    with pytest.raises(ValueError, match="cfg"):
        QueryServer(graph, cfg=EngineConfig(),
                    thresholds=Thresholds(tau_sel=0.01))
    with pytest.raises(ValueError, match="cfg"):
        QueryServer(graph, cfg=EngineConfig(), impl="ref")


def test_calibrator_bounds_anchor_to_reference_defaults():
    from repro.core.planner import PlanDecision
    plan = PlanDecision(use_check=True, complex_query=True,
                        max_selectivity=1e9, est_iterations=1e6,
                        est_join_product=1e12)
    th = Thresholds(tau_iter=1.0, tau_join=1.0, tau_sel=0.01)
    cal = Calibrator(th, CostModel())
    ref = Thresholds()
    for _ in range(100):
        cal.observe(_mk_stats(used_check=True, plan=plan,
                              candidates_before=100,
                              candidates_after=100))
    # separator evidence says tau > 1e9, but the cage anchored at the
    # reference defaults caps it
    assert th.tau_sel == ref.tau_sel * Calibrator.TAU_BOUND


# --------------------------- replay estimator --------------------------- #
def test_replay_estimator_replays_then_falls_back():
    base = JoinEstimator(None, {0: 10, 1: 10})
    # recorded entries are (rows, executed capacity) pairs
    rep = ReplayEstimator(base, [(7, 64), (42, 128)])
    e = rep.edge_join(5, None, True, 3)
    assert e == 7 and e.cap == 64
    e = rep.table_join(4, 4, (0,))
    assert e == 42 and e.cap == 128
    # cursor exhausted -> analytic fallback (no pinned capacity)
    fb = rep.table_join(4, 4, (0,))
    assert fb == base.table_join(4, 4, (0,))
    assert getattr(fb, "cap", None) is None
    # bare-int legacy entries still replay as plain row counts
    rep2 = ReplayEstimator(base, [9])
    assert rep2.table_join(4, 4, (0,)) == 9


# ------------------------- QueryStats.to_dict --------------------------- #
def test_query_stats_to_dict_schema_pinned():
    expected = {
        "used_check", "truncated", "cache_hit", "result_cache_hit",
        "candidates_before", "candidates_after",
        "prepare_time", "check_time", "match_time", "conn_time",
        "total_time", "join_work", "dtree_work",
        "join_retries", "n_estimated_joins",
        "join_est_rows", "join_actual_rows",
        "join_est_log_err", "join_est_log_bias",
        "plan_mode", "sorts_performed", "sorts_avoided",
        "edge_scan_rows",
        "plan_cost", "greedy_plan_cost",
        "conn_reach_pairs", "conn_connected_pairs",
        "conn_endpoint_rows", "conn_endpoint_distinct",
        "conn_est_pairs", "conn_est_reach_pairs",
        "budget_checks", "host_syncs", "degraded_steps",
        "join_strategies", "conn_strategies", "plan",
    }
    d = QueryStats().to_dict()
    assert set(d) == expected
    import json
    json.dumps(d)                         # JSON-serializable as-is


def test_governor_telemetry_schema_pinned(graph):
    """The governor section of QueryServer.telemetry() is a consumed
    wire format (dashboards, BENCH json): pin its flat key set, the
    breaker/rung-memory sub-schemas, and JSON-serializability."""
    import json
    from repro.serve import GovernorConfig
    srv = QueryServer(graph, impl="ref", governor=GovernorConfig())
    srv.query(random_query(graph, size=3, seed=50))
    gov = srv.telemetry()["governor"]
    assert set(gov) == {
        "limits", "shed_submit", "shed_flush", "budget_exceeded",
        "degraded_queries", "degraded_by_rung", "exhausted",
        "transient_retries", "transient_recoveries", "ladder_entries",
        "breaker", "rung_memory", "snapshot",
    }
    assert set(gov["breaker"]) == {
        "tracked", "trips", "denials", "probes", "recoveries",
        "evictions", "open", "half_open",
    }
    assert set(gov["rung_memory"]) == {
        "tracked", "hits", "jumps", "probes", "probe_recoveries",
        "probe_failures", "chronic", "evictions",
    }
    assert gov["snapshot"] is None      # nothing saved/restored yet
    json.dumps(gov)
    # after a snapshot round-trip the age/version block appears
    import tempfile, os
    path = os.path.join(tempfile.mkdtemp(), "t.snap")
    srv.save_snapshot(path)
    snap = srv.telemetry()["governor"]["snapshot"]
    assert set(snap) == {"action", "format_version", "age_s"}
    assert snap["action"] == "saved" and snap["age_s"] >= 0.0
    json.dumps(snap)


def test_query_stats_to_dict_from_execution(graph, pool):
    import json
    eng = make_engine(graph, "rdf_h", impl="ref")
    d = eng.execute(pool[1]).stats.to_dict()
    json.dumps(d)
    assert d["plan"] is not None and "max_selectivity" in d["plan"]
    assert d["join_strategies"] and isinstance(d["conn_strategies"], dict)


# ------------------------- brute-force anchor --------------------------- #
def test_server_matches_brute_force(graph):
    q = random_query(graph, size=4, seed=77, n_connection=1, d_c=2)
    want = {tuple(t[c] for c in sorted(range(q.num_nodes)))
            for t in brute_force_match(graph, q)}
    srv = QueryServer(graph, impl="ref")
    assert srv.query(q).result_set() == want    # cold
    assert srv.query(q).result_set() == want    # warm replay
