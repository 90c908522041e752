"""The benchmark's own copy of the random template sampler (paper §6).

A copy of the program's `repro.data.queries.random_query` as it stood
when the benchmark was defined, over the benchmark's own graph
(`bench.graph.Graph`).  A template is a sampled connected subgraph, so
it has at least one match; its labels are generalized into partial
keywords:

  - resource URIs: drop the id, keep the "Type/" prefix;
  - literals: cut trailing characters until the prefix matches 1..200
    labels (a random choice among the valid cuts);
  - with probability `exact_nodes` a node keeps its whole label.

`n_connection` template edges become connection edges ("a reaches b in
at most d_c directed hops").
"""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from .graph import LITERAL, Graph


@dataclass
class Template:
    keywords: list[str]
    # (src node, dst node, predicate name)
    edges: list[tuple[int, int, str]] = field(default_factory=list)
    # (src node, dst node, max directed hops)
    connections: list[tuple[int, int, int]] = field(default_factory=list)


def generalize_literal(graph: Graph, label: str, rng,
                       lo_matches: int = 1, hi_matches: int = 200) -> str:
    options = []
    for cut in range(len(label), 0, -1):
        p = label[:cut]
        c = graph.cardinality(p)
        if lo_matches <= c <= hi_matches:
            options.append(p)
        if c > hi_matches:
            break
    if not options:
        return label
    return options[rng.integers(0, len(options))]


def keyword_for_node(graph: Graph, node: int, rng) -> str:
    label = str(graph.labels[node])
    if graph.node_kind[node] == LITERAL:
        return generalize_literal(graph, label, rng)
    if "/" in label:
        return label.split("/")[0] + "/"
    return generalize_literal(graph, label, rng)


def random_query(graph: Graph, size: int = 6, seed: int = 0,
                 n_connection: int = 0, d_c: int = 4,
                 exact_nodes: float = 0.0) -> Template:
    rng = np.random.default_rng(seed)
    out_indptr, out_nbr, out_pred = graph.out_csr
    in_indptr, in_nbr, in_pred = graph.in_csr

    # templates with >= 3 copies of one keyword are resampled: k
    # interchangeable nodes multiply the answer by about |C|^k
    for _attempt in range(64):
        e0 = int(rng.integers(0, graph.num_edges))
        nodes = [int(graph.src[e0]), int(graph.dst[e0])]
        edges = [(int(graph.src[e0]), int(graph.dst[e0]),
                  int(graph.pred[e0]))]
        stall = 0
        while len(nodes) < size and stall < 200:
            v = nodes[rng.integers(0, len(nodes))]
            cands = []
            s, e = out_indptr[v], out_indptr[v + 1]
            cands += [(v, int(out_nbr[i]), int(out_pred[i]))
                      for i in range(s, e)]
            s, e = in_indptr[v], in_indptr[v + 1]
            cands += [(int(in_nbr[i]), v, int(in_pred[i]))
                      for i in range(s, e)]
            if not cands:
                stall += 1
                continue
            key = cands[rng.integers(0, len(cands))]
            if key in edges:
                stall += 1
                continue
            edges.append(key)
            for x in key[:2]:
                if x not in nodes:
                    nodes.append(x)
            stall = 0
        if len(nodes) < min(size, 3):
            continue
        keywords = []
        for g in nodes:
            if rng.random() < exact_nodes:
                keywords.append(str(graph.labels[g]))
            else:
                keywords.append(keyword_for_node(graph, g, rng))
        if max(Counter(keywords).values()) <= 2:
            break
    node_idx = {g: i for i, g in enumerate(nodes)}

    qedges = [(node_idx[s], node_idx[d], str(graph.predicates[p]))
              for s, d, p in edges]
    conns = []
    rng.shuffle(qedges)
    for _ in range(min(n_connection, max(len(qedges) - 1, 0))):
        s, d, _p = qedges.pop()
        conns.append((s, d, d_c))
    return Template(keywords=keywords, edges=qedges, connections=conns)
