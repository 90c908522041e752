"""The benchmark's reference against the program's brute-force matcher
(`repro.core.query.brute_force_match`) on tiny graphs."""
import pytest

from bench.graph import Graph
from bench.queries import random_query
from bench.reference import Reference
from repro.core import brute_force_match
from repro.core.query import ConnectionEdge, QueryEdge, QueryTemplate
from repro.data import random_graph


def _as_program(graph, tpl):
    return QueryTemplate(
        tpl.keywords,
        [QueryEdge(s, d, graph.predicate_id(p)) for s, d, p in tpl.edges],
        [ConnectionEdge(s, d, h) for s, d, h in tpl.connections])


@pytest.mark.parametrize("seed", range(8))
def test_bench_reference_matches_brute_force(seed):
    pg = random_graph(40, 90, 3, 10, seed=seed)
    g = Graph(pg.triples(), set(pg.labels[pg.node_kind == 1].tolist()))
    assert (g.labels == pg.labels).all()
    ref = Reference(g)
    for i in range(5):
        tpl = random_query(g, size=3 + i % 3, seed=seed * 10 + i,
                           n_connection=i % 2, d_c=2, exact_nodes=0.3)
        want = brute_force_match(pg, _as_program(pg, tpl))
        got = {tuple(int(x) for x in row) for row in ref.match(tpl)}
        assert got == want, tpl


def test_bench_reference_connection_is_directed_within_hops():
    # a -> b -> c -> d, and e alone: d is 3 hops from a, a none from d
    triples = [("N/a", "p", "N/b"), ("N/b", "p", "N/c"), ("N/c", "p", "N/d"),
               ("N/e", "p", "N/e2")]
    g = Graph(triples)
    from bench.queries import Template
    ref = Reference(g)
    ids = {str(x): i for i, x in enumerate(g.labels)}
    near = ref.match(Template(["N/a", "N/d"], [], [(0, 1, 3)]))
    assert [tuple(r) for r in near] == [(ids["N/a"], ids["N/d"])]
    assert len(ref.match(Template(["N/a", "N/d"], [], [(0, 1, 2)]))) == 0
    assert len(ref.match(Template(["N/d", "N/a"], [], [(0, 1, 5)]))) == 0


def test_bench_reference_limits_end_the_match():
    triples = [(f"A/{i}", "p", f"B/{j}") for i in range(30) for j in range(30)]
    from bench.queries import Template
    tpl = Template(["A/", "B/"], [(0, 1, "p")], [])
    assert len(Reference(Graph(triples)).match(tpl)) == 900
    assert Reference(Graph(triples), max_rows=899).match(tpl) is None
    assert Reference(Graph(triples), max_intermediate=899).match(tpl) is None
