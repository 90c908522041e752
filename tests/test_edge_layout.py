"""The predicate-grouped edge layout behind `edge_pairs`.

A scan of a named predicate reads only that predicate's group of
`RDFGraph.edge_layout`; the oracle is a numpy scan of the graph's original
edge arrays, which the grouped scan must match row for row, in order, with
the same count and capacity.  Bypass cases (any predicate, a predicate with
more than half the edges, no edges) and `Dataset.apply_delta` versions are
pinned too, as is the `edge_scan_rows` counter that says which arrays ran.
"""
from dataclasses import replace
from types import SimpleNamespace

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import Dataset, JoinTelemetry, make_engine
from repro.core.graph import RDFGraph
from repro.core.matching import _pow2, edge_pairs
from repro.core.query import brute_force_match
from repro.data import random_query
from repro.obs.trace import host_syncs

# edges per predicate: p0 holds more than half (full scan); the rest are
# uneven, down to groups under the 64-row floor
COUNTS = (1100, 400, 250, 130, 60, 40, 15, 5)
N_NODES = 300
E = sum(COUNTS)


def _skewed_graph(seed=0) -> RDFGraph:
    rng = np.random.default_rng(seed)
    pred = rng.permutation(np.repeat(np.arange(len(COUNTS)), COUNTS))
    src = rng.integers(0, N_NODES, E)
    dst = rng.integers(0, N_NODES, E)
    loops = rng.random(E) < 0.05           # self-loops in every group
    dst[loops] = src[loops]
    names = [f"n/{i:04d}" for i in range(N_NODES)]
    return RDFGraph.from_triples(
        [(names[s], f"p{p}", names[d]) for s, p, d in zip(src, pred, dst)])


@pytest.fixture(scope="module")
def graph():
    return _skewed_graph()


def _specs(graph, kind, seed):
    """Endpoint pass specs (device form, numpy [N] bool form)."""
    n = graph.num_nodes
    rng = np.random.default_rng(seed)
    masks = [rng.random(n) < 0.6 for _ in range(2)]
    ivs = [tuple(sorted(rng.integers(0, n + 1, 2))) for _ in range(2)]
    out = []
    for i, use_iv in enumerate({"mask": (False, False),
                                "interval": (True, True),
                                "mixed": (False, True)}[kind]):
        if use_iv:
            lo, hi = (int(v) for v in ivs[i])
            m = np.zeros(n, bool)
            m[lo:hi] = True
            out.append(((jnp.int32(lo), jnp.int32(hi)), m))
        else:
            out.append((jnp.asarray(masks[i]), masks[i]))
    return out


def _oracle(graph, pred_id, m_src, m_dst, loop=False):
    """Rows of a scan of the original arrays, in edge order."""
    src, dst = graph.src, graph.dst
    keep = m_src[src] & m_dst[dst]
    if pred_id is not None:
        keep &= graph.pred == pred_id
    if loop:
        keep &= src == dst
        return src[keep][:, None]
    return np.stack([src[keep], dst[keep]], axis=1)


def _scan(graph, pred_id, spec_a, spec_b, cols):
    tel = JoinTelemetry()
    out = edge_pairs(graph, pred_id, spec_a, spec_b, cols, telemetry=tel)
    return out, tel.edge_scan_rows


def _group_len(c):
    return E if 2 * c > E else _pow2(c)


@pytest.mark.parametrize("direction", ["out", "in"])
@pytest.mark.parametrize("kind", ["mask", "interval", "mixed"])
@pytest.mark.parametrize("pred_id", range(len(COUNTS)))
def test_grouped_scan_matches_full_scan(graph, pred_id, kind, direction):
    """Same rows in the same order, count and cap as a scan of the
    original arrays; it reads the group alone unless p holds over half."""
    (dev_a, np_a), (dev_b, np_b) = _specs(graph, kind, seed=pred_id)
    p = graph.predicate_id(f"p{pred_id}")
    if direction == "out":
        out, scanned = _scan(graph, p, dev_a, dev_b, (0, 1))
        want = _oracle(graph, p, np_a, np_b)
    else:                          # the D-tree's incoming edge: specs swap
        out, scanned = _scan(graph, p, dev_b, dev_a, (1, 0))
        want = _oracle(graph, p, np_b, np_a)
    assert out.cols == ((0, 1) if direction == "out" else (1, 0))
    assert out.count == len(want) and out.cap == _pow2(len(want))
    np.testing.assert_array_equal(out.numpy(), want)
    assert (np.asarray(out.rows)[out.count:] == -1).all()
    assert scanned == _group_len(COUNTS[pred_id])


@pytest.mark.parametrize("pred_id", range(len(COUNTS)))
def test_grouped_self_loop_scan_matches_full_scan(graph, pred_id):
    """A template self-loop: one node, so one spec on both ends."""
    (dev_a, np_a), _ = _specs(graph, "mask", seed=100 + pred_id)
    p = graph.predicate_id(f"p{pred_id}")
    out, scanned = _scan(graph, p, dev_a, dev_a, (2, 2))
    want = _oracle(graph, p, np_a, np_a, loop=True)
    assert want.size > 0 or COUNTS[pred_id] < 100
    assert out.cols == (2,)
    assert out.count == len(want) and out.cap == _pow2(len(want))
    np.testing.assert_array_equal(out.numpy(), want)
    assert scanned == _group_len(COUNTS[pred_id])


def test_group_holds_the_predicates_edges_in_edge_order(graph):
    layout = graph.edge_layout
    assert graph.edge_layout is layout             # built once per graph
    np.testing.assert_array_equal(layout.counts, COUNTS)
    for p, c in enumerate(COUNTS):
        group = layout.groups[p]
        if 2 * c > E:
            assert group is None
            continue
        src, dst, pred = (np.asarray(a) for a in group)
        assert len(src) == len(dst) == len(pred) == _pow2(c)
        sel = graph.pred == p
        np.testing.assert_array_equal(src[:c], graph.src[sel])
        np.testing.assert_array_equal(dst[:c], graph.dst[sel])
        assert (pred[:c] == p).all() and (pred[c:] == -1).all()
        assert (src[c:] == 0).all() and (dst[c:] == 0).all()


def test_any_predicate_scans_the_full_arrays(graph):
    (dev_a, np_a), (dev_b, np_b) = _specs(graph, "mixed", seed=7)
    assert graph.edge_layout.arrays(None) is graph.edge_layout.full
    out, scanned = _scan(graph, None, dev_a, dev_b, (0, 1))
    assert scanned == E
    want = _oracle(graph, None, np_a, np_b)
    assert out.count == len(want) and out.cap == _pow2(len(want))
    np.testing.assert_array_equal(out.numpy(), want)


def test_predicate_over_half_the_edges_scans_the_full_arrays(graph):
    p = graph.predicate_id("p0")
    assert 2 * COUNTS[0] > E
    assert graph.edge_layout.arrays(p) is graph.edge_layout.full
    (dev_a, _), (dev_b, _) = _specs(graph, "mask", seed=8)
    assert _scan(graph, p, dev_a, dev_b, (0, 1))[1] == E


@pytest.mark.parametrize("loop", [False, True])
def test_predicate_without_edges_gives_an_empty_table(graph, loop):
    """A predicate the graph names but no edge carries, and an id past
    the graph's predicates: an empty table, with no scan and no read."""
    named = replace(graph, predicates=np.append(graph.predicates, "zz"),
                    pred_kind=np.append(graph.pred_kind, 0))
    cols = (3, 3) if loop else (3, 4)
    (dev_a, _), (dev_b, _) = _specs(graph, "mixed", seed=9)
    for g, p in ((named, len(COUNTS)), (graph, len(COUNTS) + 5)):
        syncs = host_syncs()
        out, scanned = _scan(g, p, dev_a, dev_b, cols)
        assert host_syncs() == syncs and scanned == 0
        assert out.count == 0 and out.cols == cols[:1 if loop else 2]
        assert out.cap == _pow2(0) and out.numpy().shape == (0, len(out.cols))


@pytest.mark.parametrize("new_predicate", [False, True])
def test_apply_delta_version_gets_its_own_layout(graph, new_predicate):
    """Inserted edges of an existing predicate (incremental path) and of
    a new one (rebuild) are seen by the new version's scans; the old
    version's layout is left as it was."""
    ds = Dataset.build(graph, variant="rdf_h")
    old = graph.edge_layout
    lab = graph.labels
    rng = np.random.default_rng(5)
    ends = rng.integers(0, graph.num_edges, (2, 12))
    inserts = [(lab[graph.src[i]], "p3", lab[graph.dst[j]]) for i, j in ends.T]
    if new_predicate:
        inserts += [(lab[graph.src[i]], "q-new", lab[graph.dst[i]])
                    for i in ends[0, :6]]
    new = ds.apply_delta(inserts)
    assert new.delta_info["mode"] == ("rebuild" if new_predicate
                                      else "incremental")
    g2 = new.graph
    assert g2.edge_layout is not old and graph.edge_layout is old
    n = g2.num_nodes
    every = np.ones(n, bool)
    names = ["p3"] + (["q-new"] if new_predicate else [])
    for name in names:
        p = g2.predicate_id(name)
        out, scanned = _scan(g2, p, jnp.asarray(every), (jnp.int32(0),
                                                          jnp.int32(n)),
                             (0, 1))
        want = _oracle(g2, p, every, every)
        np.testing.assert_array_equal(out.numpy(), want)
        assert scanned == _pow2(int((g2.pred == p).sum())) < g2.num_edges
    p3 = g2.predicate_id("p3")
    assert int((g2.pred == p3).sum()) == COUNTS[3] + 12


@pytest.mark.parametrize("variant", ["stwig+", "h2", "spath_ni2"])
def test_engine_answers_and_scan_count_on_skewed_graph(graph, variant):
    """Check on and off: exact answers, and no execution scans more edge
    rows than one full scan per template edge."""
    eng = make_engine(Dataset.build(graph, variant=variant), variant,
                      impl="ref")
    for seed in range(3):
        q = random_query(graph, size=3, seed=seed, exact_nodes=0.5)
        r = eng.execute(q)
        assert r.result_set() == brute_force_match(graph, q)
        assert 0 < r.stats.edge_scan_rows <= len(q.edges) * E


def test_edge_scan_rows_reader_means_over_answered_requests():
    from bench.harness import Window, load_metric
    read = load_metric("join.edge_scan_rows")

    def window(stats):
        reqs = [SimpleNamespace(result=SimpleNamespace(stats=s))
                for s in stats]
        return Window(requests=reqs, executions=len(reqs))
    w = window([SimpleNamespace(edge_scan_rows=1024),
                SimpleNamespace(edge_scan_rows=512)])
    assert read(w) == pytest.approx(768.0)
    assert read(window([])) is None
    # a program that does not count the rows scanned
    assert read(window([SimpleNamespace(host_syncs=3)])) is None
