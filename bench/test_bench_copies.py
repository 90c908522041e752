"""The benchmark's copy of the template sampler gives what the
program's `repro.data.random_query` gives, on the program's generators'
graphs at a small scale."""
import pytest

from bench.graph import Graph
from bench.queries import random_query
from repro.data import random_query as program_random_query
from repro.data import rdf_gen as program_gen


@pytest.mark.parametrize("name", ["dblp_like", "imdb_like", "lubm_like",
                                  "sp2b_like"])
def test_bench_sampler_copy_matches_program(name):
    pg = getattr(program_gen, name)(0.02, seed=3)
    g = Graph([tuple(str(x) for x in t) for t in pg.triples()],
              set(pg.labels[pg.node_kind == 1].tolist()))
    assert (g.labels == pg.labels).all()
    assert (g.node_kind == pg.node_kind).all()
    for i in range(6):
        kw = dict(size=5 + i % 2, seed=100 + i, n_connection=i % 2, d_c=3,
                  exact_nodes=0.5)
        mine, theirs = random_query(g, **kw), program_random_query(pg, **kw)
        assert mine.keywords == theirs.keywords
        assert mine.edges == [(e.src, e.dst, str(pg.predicates[e.pred]))
                              for e in theirs.edges]
        assert mine.connections == [(c.src, c.dst, c.max_dist)
                                    for c in theirs.connections]
