"""The read tape of warm plan replay (`PreparedQuery.read_tape`).

A warm execution's 0-d count reads are recorded on the first warm run
and answered from the tape on later ones; the final read checks every
answer against the device.  Under test:

  * exactness: replayed runs return the cold run's rows and the
    brute-force matcher's, over every join strategy, connection edges
    and a template self-loop;
  * what a replay reads: the data reads and the final read, nothing
    else, one ``sync`` span each;
  * a wrong tape costs one real re-execution and never a wrong row;
  * where a tape does not hold: a new dataset version, another engine
    config, a restored snapshot, a reset plan;
  * injected capacity faults during a replay resolve exact or typed;
  * the benchmark's reader of `replayed_reads`.
"""
from dataclasses import replace
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import Dataset, Thresholds
from repro.core.dataset import interval_footprint_hit
from repro.core.engine import EngineConfig
from repro.core.graph import RDFGraph
from repro.core.query import (ConnectionEdge, QueryEdge, QueryTemplate,
                              brute_force_match)
from repro.data import random_query
from repro.obs import Tracer
from repro.serve import GovernorConfig, QueryServer, ServingError
from repro.testing import Fault, FaultInjector

N_NODES = 90


@pytest.fixture(scope="module")
def graph():
    """Three predicates over two label families, with self-loops."""
    rng = np.random.default_rng(5)
    e = 320
    src = rng.integers(0, N_NODES, e)
    dst = rng.integers(0, N_NODES, e)
    loops = rng.random(e) < 0.08
    dst[loops] = src[loops]
    pred = rng.integers(0, 3, e)
    names = [f"{'AB'[i % 2]}/{i:04d}" for i in range(N_NODES)]
    return RDFGraph.from_triples(
        [(names[s], f"p{p}", names[d]) for s, p, d in zip(src, pred, dst)])


@pytest.fixture(scope="module")
def pool(graph):
    loop = QueryTemplate(keywords=["A/", "B/"],
                         edges=[QueryEdge(0, 0, graph.predicate_id("p0")),
                                QueryEdge(0, 1, graph.predicate_id("p1"))])
    chain = QueryTemplate(keywords=["A/", "B/", "A/"],
                          edges=[QueryEdge(0, 1, graph.predicate_id("p2")),
                                 QueryEdge(1, 2, graph.predicate_id("p1"))],
                          connections=[ConnectionEdge(0, 2, 2)])
    return [loop, chain] + [
        random_query(graph, size=4, seed=70 + i, n_connection=i % 2, d_c=2)
        for i in range(3)]


@pytest.fixture(scope="module")
def oracle(graph, pool):
    return [brute_force_match(graph, q) for q in pool]


# one config per join strategy, plus the staged sort-merge chain and the
# reach-join connection path
CFGS = {
    "nested": dict(join_impl="nested"),
    "sorted": dict(join_impl="sorted"),
    "staged": dict(join_impl="sorted", fuse_joins=False),
    "radix": dict(join_impl="radix"),
    "reach": dict(join_impl="sorted", connection_impl="reach"),
}


def _cfg(name: str) -> EngineConfig:
    return EngineConfig(impl="ref", thresholds=Thresholds(nested_join_max=1),
                        **CFGS[name])


def _tape(srv, q):
    from repro.serve import canonicalize
    return srv.plan_cache.peek(srv.dataset_id, canonicalize(q)[2])


@pytest.mark.parametrize("name", sorted(CFGS))
def test_replays_are_exact_and_read_only_data_and_the_final_rows(
        graph, pool, oracle, name):
    srv = QueryServer(graph, cfg=_cfg(name), tracer=Tracer())
    forced = CFGS[name]["join_impl"]
    used = set()
    for q, want in zip(pool, oracle):
        cold = srv.query(q)
        rec = srv.query(q)                       # first warm run records
        assert not cold.stats.cache_hit and rec.stats.cache_hit
        assert rec.stats.replayed_reads == 0
        tape = _tape(srv, q).read_tape[1]
        assert tape and all(np.ndim(v) == 0 for _, v in tape)
        assert len(tape) < rec.stats.host_syncs
        for _ in range(2):
            f = srv.submit(q)
            res = f.result()
            qs = res.stats
            assert res.result_set() == cold.result_set() == want
            assert qs.replayed_reads == len(tape) > 0
            assert qs.replay_mismatches == 0
            # the recording run's reads less its taped ones: its data
            # reads plus the final one
            assert qs.host_syncs == rec.stats.host_syncs - len(tape)
            spans = srv.tracer.get(f.trace_id).spans
            syncs = [s.attrs["what"] for s in spans if s.name == "sync"]
            assert len(syncs) == qs.host_syncs and syncs[-1] == "rows"
            seg, = [s for s in spans if s.name == "execute"]
            assert seg.attrs["replayed"] == len(tape)
            used |= set(qs.join_strategies)
    assert forced in used


@pytest.mark.parametrize("plant", ["first_plus_one", "last_zero",
                                   "every_zero"])
def test_a_wrong_tape_costs_one_real_execution_never_a_wrong_row(
        graph, pool, oracle, plant):
    srv = QueryServer(graph, cfg=_cfg("sorted"))
    for q, want in zip(pool, oracle):
        srv.query(q)
        srv.query(q)
        pq = _tape(srv, q)
        stamp, good = pq.read_tape
        bad = list(good)
        if plant == "first_plus_one":
            bad[0] = (bad[0][0], bad[0][1] + 1)
        elif plant == "last_zero":
            bad[-1] = (bad[-1][0], np.zeros_like(bad[-1][1]) + 0)
        else:
            bad = [(w, np.zeros_like(v)) for w, v in bad]
        pq.read_tape = (stamp, bad)
        res = srv.query(q)
        assert res.result_set() == want
        refuted = any(int(b) != int(g) for (_, b), (_, g) in zip(bad, good))
        assert res.stats.replay_mismatches == int(refuted)
        assert [(w, int(v)) for w, v in pq.read_tape[1]] == \
            [(w, int(v)) for w, v in good]       # recorded afresh
        again = srv.query(q)
        assert again.result_set() == want
        assert again.stats.replayed_reads == len(good)
        assert again.stats.replay_mismatches == 0


def test_a_tape_out_of_step_stops_replay_and_is_recorded_again(
        graph, pool, oracle):
    srv = QueryServer(graph, cfg=_cfg("sorted"))
    q, want = pool[2], oracle[2]
    srv.query(q)
    srv.query(q)
    pq = _tape(srv, q)
    stamp, good = pq.read_tape
    pq.read_tape = (stamp, [good[0], ("no_such_read", good[1][1])]
                    + good[2:])
    res = srv.query(q)
    assert res.result_set() == want
    assert res.stats.replayed_reads == 1 and res.stats.replay_mismatches == 0
    assert pq.read_tape is None
    srv.query(q)                                 # records
    assert srv.query(q).stats.replayed_reads == len(good)


def _footprint_clean_delete(srv, graph, q):
    """A one-edge delete that misses the template's candidate intervals
    and is absorbed incrementally, so its plan stays warm."""
    pq = _tape(srv, q)
    iv = [(int(lo), int(hi)) for lo, hi in pq.iv]
    ds = srv.dataset
    for i in range(graph.num_edges):
        t = (graph.labels[graph.src[i]], graph.predicates[graph.pred[i]],
             graph.labels[graph.dst[i]])
        trial = ds.apply_delta(deletes=[t])
        if trial.delta_info["mode"] == "incremental" \
                and not interval_footprint_hit(iv, trial.touched):
            return t
    return None


def test_a_new_dataset_version_reads_for_real(graph):
    srv = QueryServer(Dataset.build(graph), cfg=_cfg("sorted"),
                      calibrate=False)
    # a template over the B/ family alone leaves A/ nodes to delete from
    q = QueryTemplate(keywords=["B/", "B/"],
                      edges=[QueryEdge(0, 1, graph.predicate_id("p0"))])
    for _ in range(3):
        srv.query(q)
    assert srv.query(q).stats.replayed_reads > 0
    chosen = _footprint_clean_delete(srv, graph, q)
    assert chosen is not None
    info = srv.apply_delta(deletes=[chosen])
    assert info["plans_kept"] == 1
    first = srv.query(q)
    assert first.stats.cache_hit and first.stats.replayed_reads == 0
    want = brute_force_match(srv.dataset.graph, q)
    assert first.result_set() == want
    nxt = srv.query(q)
    assert nxt.stats.replayed_reads > 0 and nxt.result_set() == want


def test_a_restored_snapshot_carries_no_tape(graph, pool, oracle, tmp_path):
    srv = QueryServer(graph, cfg=_cfg("sorted"), governor=GovernorConfig())
    for _ in range(3):
        for q in pool:
            srv.query(q)
    path = tmp_path / "serve.snap"
    srv.save_snapshot(path)
    srv2 = QueryServer(graph, cfg=_cfg("sorted"), governor=GovernorConfig())
    srv2.restore_snapshot(path)
    for q, want in zip(pool, oracle):
        assert _tape(srv2, q).read_tape is None
        first = srv2.query(q)
        assert first.stats.cache_hit and first.stats.replayed_reads == 0
        nxt = srv2.query(q)
        assert nxt.stats.replayed_reads > 0
        assert first.result_set() == nxt.result_set() == want


def test_a_tape_holds_for_the_engine_that_recorded_it(graph, pool):
    srv = QueryServer(graph, cfg=_cfg("sorted"))
    q = pool[3]
    for _ in range(3):
        srv.query(q)
    pq = _tape(srv, q)
    assert pq.read_tape[0]() is srv.engine
    # the calibrator moves the live cost model in place: warm replay
    # does not plan, so the tape still holds
    srv.engine.cfg.cost_model.join_est_scale *= 1.5
    assert srv.query(q).stats.replayed_reads == len(pq.read_tape[1]) > 0
    other = srv.engine.with_config(replace(srv.engine.cfg,
                                           join_impl="nested"))
    res = other.execute_prepared(pq)
    assert res.stats.replayed_reads == 0          # recorded for `other`
    assert pq.read_tape[0]() is other
    assert other.execute_prepared(pq).stats.replayed_reads > 0
    pq.reset_learned()
    assert pq.read_tape is None


@pytest.mark.parametrize("point", ["fused_probe", "radix_probe",
                                   "kernel_dispatch"])
@pytest.mark.parametrize("every", [None, 2])
def test_corrupt_capacity_during_a_replay_is_exact_or_typed(
        graph, pool, oracle, point, every):
    name = {"fused_probe": "sorted", "radix_probe": "radix",
            "kernel_dispatch": "staged"}[point]
    srv = QueryServer(graph, cfg=_cfg(name), governor=GovernorConfig())
    for _ in range(2):                           # cold, then recording
        for f in srv.submit_many(pool, wait=True):
            f.result()
    fault = (Fault(point, "corrupt_capacity", at=1) if every is None
             else Fault(point, "corrupt_capacity", every=every))
    replayed = 0
    with FaultInjector(fault) as fi:
        futures = srv.submit_many(pool, wait=True)
        assert all(f.done() for f in futures)
        for f, want in zip(futures, oracle):
            try:
                res = f.result()
            except ServingError:
                continue
            assert res.result_set() == want
            replayed += res.stats.replayed_reads
    assert fi.fired and replayed > 0
    # the plans replay exactly once the fault has gone
    for q, want in zip(pool, oracle):
        res = srv.query(q)
        assert res.result_set() == want


def test_replayed_reads_reader_means_over_answered_requests():
    from bench.harness import Window, load_metric
    read = load_metric("serve.replayed_reads")

    def window(stats):
        reqs = [SimpleNamespace(result=SimpleNamespace(stats=s))
                for s in stats]
        return Window(requests=reqs, executions=len(reqs))
    w = window([SimpleNamespace(replayed_reads=12),
                SimpleNamespace(replayed_reads=9)])
    assert read(w) == pytest.approx(10.5)
    assert read(window([])) is None
    # a program that does not count replayed reads
    assert read(window([SimpleNamespace(host_syncs=3)])) is None


def test_a_tape_answers_the_thread_that_opened_it_alone():
    import threading

    import jax.numpy as jnp

    from repro.obs.trace import ReadTape, host_read
    x = jnp.int32(7)
    seen = []
    with ReadTape([("n", np.int32(3))]) as tape:
        t = threading.Thread(
            target=lambda: seen.append(int(host_read(x, "n"))))
        t.start()
        t.join()
        assert int(host_read(x, "n")) == 3
    assert seen == [7] and tape.kept == [x]
    assert int(host_read(x, "n")) == 7           # the tape is closed
