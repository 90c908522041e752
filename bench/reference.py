"""The benchmark's plain reference matcher.

The same semantics as the program (paper §1.1, subgraph isomorphism):
every template node maps to a distinct graph node whose label starts
with the node's keyword, every predicate edge to a graph edge with that
predicate, and every connection edge (a, b, h) to a directed path of at
most h hops from a's node to b's.  The answer is the set of distinct
rows, one column per template node.

It shares no code or data with the program: it reads the benchmark's
own graph (`bench.graph.Graph`) and plans nothing.  In order:

  1. each node's candidates are its keyword's label interval;
  2. arc consistency: each predicate edge's graph edges are filtered by
     both endpoint candidate sets, which shrink to the endpoints left,
     until nothing changes;
  3. per component of predicate edges, the edges are joined one at a
     time, smallest first among those touching the bound nodes;
  4. connection edges filter a table (both ends in it) or join two
     tables through the pairs a breadth-first search of at most h hops
     reaches;
  5. tables still apart are crossed, rows with a repeated node dropped.

Any intermediate table over `max_intermediate` rows, two tables joined
by a connection edge whose cross product passes it, or an answer over
`max_rows`, ends the match with None: such a template is left out of a
pool, never half answered.  `within_guard` says whether every table any
join order could build stays within `max_intermediate` rows; a pool
takes only templates for which it does, with the limit at the
deployment's row guard, so that the engine can answer them whole.
"""
from __future__ import annotations

import numpy as np

from .graph import Graph
from .queries import Template


class TooLarge(Exception):
    """An intermediate table or the answer passed its limit."""


class Reference:
    def __init__(self, graph: Graph, max_rows: int = 65536,
                 max_intermediate: int = 1 << 22):
        self.graph = graph
        self.max_rows = max_rows
        self.max_intermediate = max_intermediate
        order = np.argsort(graph.pred, kind="stable")
        bounds = np.searchsorted(graph.pred[order],
                                 np.arange(len(graph.predicates) + 1))
        self._by_pred = {str(graph.predicates[i]):
                         order[bounds[i]:bounds[i + 1]]
                         for i in range(len(graph.predicates))}

    # ---------------------------------------------------------------- #
    def match(self, tpl: Template) -> np.ndarray | None:
        """[rows, template nodes] distinct answers in graph node ids,
        sorted; None when a limit was passed."""
        try:
            return self._match(tpl)
        except TooLarge:
            return None

    def _check(self, n: int) -> None:
        if n > self.max_intermediate:
            raise TooLarge(n)

    def _match(self, tpl: Template) -> np.ndarray:
        g = self.graph
        n_q = len(tpl.keywords)
        dom = []
        for kw in tpl.keywords:
            lo, hi = g.interval(kw)
            m = np.zeros(g.num_nodes, dtype=bool)
            m[lo:hi] = True
            dom.append(m)

        rels = []
        for s, d, p in tpl.edges:
            idx = self._by_pred.get(p, np.zeros(0, dtype=np.int64))
            rels.append(np.stack([g.src[idx], g.dst[idx]], axis=1))
        # arc consistency over the predicate edges
        changed = True
        while changed:
            changed = False
            for k, (s, d, _p) in enumerate(tpl.edges):
                r = rels[k]
                r = r[dom[s][r[:, 0]] & dom[d][r[:, 1]]]
                if s == d:
                    r = r[r[:, 0] == r[:, 1]]
                rels[k] = r
                for q, col in ((s, 0), (d, 1)):
                    keep = np.zeros(g.num_nodes, dtype=bool)
                    keep[r[:, col]] = True
                    new = dom[q] & keep
                    if new.sum() < dom[q].sum():
                        dom[q] = new
                        changed = True
        rels = [np.unique(r, axis=0) for r in rels]

        # one table per component of predicate edges: {node: column}
        tables = []
        placed = set()
        todo = set(range(len(tpl.edges)))
        while todo:
            k0 = min(todo, key=lambda k: len(rels[k]))
            todo.discard(k0)
            self._check(len(rels[k0]))
            s, d, _ = tpl.edges[k0]
            tab = {s: rels[k0][:, 0]}
            if d != s:
                tab[d] = rels[k0][:, 1]
                tab = _distinct_filter(tab, d)
            while True:
                touching = [k for k in todo
                            if tpl.edges[k][0] in tab
                            or tpl.edges[k][1] in tab]
                if not touching:
                    break
                k = min(touching, key=lambda k: len(rels[k]))
                todo.discard(k)
                tab = self._join_edge(tab, tpl.edges[k], rels[k])
            placed.update(tab)
            tables.append(tab)
        for q in range(n_q):
            if q not in placed:
                tables.append({q: np.nonzero(dom[q])[0].astype(np.int32)})
                self._check(len(tables[-1][q]))

        for a, b, h in tpl.connections:
            ia = next(i for i, t in enumerate(tables) if a in t)
            ib = next(i for i, t in enumerate(tables) if b in t)
            if ia == ib:
                t = tables[ia]
                pairs = self._reach_pairs(np.unique(t[a]), h, None)
                keep = np.isin(_pack(t[a], t[b], g.num_nodes), pairs)
                tables[ia] = {q: c[keep] for q, c in t.items()}
                continue
            ta, tb = tables[ia], tables[ib]
            self._check(len(ta[a]) * len(tb[b]))
            allowed = np.zeros(g.num_nodes, dtype=bool)
            allowed[tb[b]] = True
            pairs = self._reach_pairs(np.unique(ta[a]), h, allowed)
            link = {a: (pairs // g.num_nodes).astype(np.int32),
                    b: (pairs % g.num_nodes).astype(np.int32)}
            merged = self._join_tables(self._join_tables(ta, link, a), tb, b)
            tables = [t for i, t in enumerate(tables) if i not in (ia, ib)]
            tables.append(merged)

        tab = tables[0]
        for t in tables[1:]:
            tab = self._cross(tab, t)
        rows = np.stack([tab[q] for q in range(n_q)], axis=1)
        rows = np.unique(rows, axis=0)
        if len(rows) > self.max_rows:
            raise TooLarge(len(rows))
        return rows

    # ---------------------------------------------------------------- #
    def _join_edge(self, tab, edge, rel):
        s, d, _ = edge
        if s in tab and d in tab:
            n = self.graph.num_nodes
            keep = np.isin(_pack(tab[s], tab[d], n),
                           _pack(rel[:, 0], rel[:, 1], n))
            return {q: c[keep] for q, c in tab.items()}
        if s in tab:
            return self._join_tables(tab, {s: rel[:, 0], d: rel[:, 1]}, s)
        return self._join_tables(tab, {d: rel[:, 1], s: rel[:, 0]}, d)

    def _join_tables(self, left, right, key, distinct=True):
        """Equi-join on one shared node; other shared nodes must agree;
        with `distinct`, rows that repeat a graph node across nodes are
        dropped."""
        order = np.argsort(right[key], kind="stable")
        rkey = right[key][order]
        lo = np.searchsorted(rkey, left[key], side="left")
        hi = np.searchsorted(rkey, left[key], side="right")
        counts = hi - lo
        total = int(counts.sum())
        self._check(total)
        li = np.repeat(np.arange(len(counts)), counts)
        starts = np.repeat(lo - np.concatenate([[0], np.cumsum(counts)[:-1]]),
                           counts)
        ri = order[np.arange(total) + starts]
        out = {q: c[li] for q, c in left.items()}
        keep = np.ones(total, dtype=bool)
        for q, c in right.items():
            col = c[ri]
            if q in out:
                keep &= out[q] == col
            else:
                out[q] = col
        out = {q: c[keep] for q, c in out.items()}
        if not distinct:
            return out
        return _distinct_filter(out, *[q for q in right if q not in left])

    # ---------------------------------------------------------------- #
    def within_guard(self, tpl: Template) -> bool:
        """Whether every table a join engine can build for `tpl` holds at
        most `max_intermediate` rows.  Such a table is the bag of matches
        of a connected set of the template's predicate edges (no node
        distinctness yet, parallel graph edges counted apart, every node's
        candidates its whole keyword interval), or the cross product of
        two components a connection edge joins; each is counted here."""
        g = self.graph
        iv = [g.interval(k) for k in tpl.keywords]
        rels = []
        for s, d, p in tpl.edges:
            idx = self._by_pred.get(p, np.zeros(0, dtype=np.int64))
            src, dst = g.src[idx], g.dst[idx]
            keep = ((src >= iv[s][0]) & (src < iv[s][1])
                    & (dst >= iv[d][0]) & (dst < iv[d][1]))
            if s == d:
                keep &= src == dst
            rels.append(np.stack([src[keep], dst[keep]], axis=1))
        n_e = len(tpl.edges)
        touch = [{k for k in range(n_e)
                  if set(tpl.edges[k][:2]) & set(tpl.edges[j][:2])}
                 for j in range(n_e)]
        subsets, frontier = set(), {frozenset([k]) for k in range(n_e)}
        while frontier:
            subsets |= frontier
            frontier = {sub | {k} for sub in frontier
                        for j in sub for k in touch[j] - sub} - subsets
        size = {}
        try:
            for sub in sorted(subsets, key=len):
                size[sub] = self._bag_size(tpl, rels, sub)
        except TooLarge:
            return False
        comp_size = {}
        for sub, n in size.items():
            nodes = {q for k in sub for q in tpl.edges[k][:2]}
            for q in nodes:
                if len(nodes) > len(comp_size.get(q, ((), 0))[0]):
                    comp_size[q] = (nodes, n)
        for q, (lo, hi) in enumerate(iv):
            comp_size.setdefault(q, ({q}, hi - lo))
        for a, b, _h in tpl.connections:
            (na, sa), (nb, sb) = comp_size[a], comp_size[b]
            if na != nb and sa * sb > self.max_intermediate:
                return False
        return True

    def _bag_size(self, tpl, rels, sub) -> int:
        n = self.graph.num_nodes
        todo = set(sub)
        k0 = min(todo, key=lambda k: len(rels[k]))
        todo.discard(k0)
        self._check(len(rels[k0]))
        s, d, _ = tpl.edges[k0]
        tab = {s: rels[k0][:, 0], d: rels[k0][:, 1]}
        while todo:
            k = min((k for k in todo if tpl.edges[k][0] in tab
                     or tpl.edges[k][1] in tab), key=lambda k: len(rels[k]))
            todo.discard(k)
            s, d, _ = tpl.edges[k]
            r = rels[k]
            if s in tab and d in tab:
                left = dict(tab, _key=_pack(tab[s], tab[d], n))
                tab = self._join_tables(
                    left, {"_key": _pack(r[:, 0], r[:, 1], n)}, "_key",
                    distinct=False)
                del tab["_key"]
            elif s in tab:
                tab = self._join_tables(tab, {s: r[:, 0], d: r[:, 1]}, s,
                                        distinct=False)
            else:
                tab = self._join_tables(tab, {d: r[:, 1], s: r[:, 0]}, d,
                                        distinct=False)
        return len(next(iter(tab.values())))

    def _cross(self, left, right):
        n_l = len(next(iter(left.values())))
        n_r = len(next(iter(right.values())))
        self._check(n_l * n_r)
        out = {q: np.repeat(c, n_r) for q, c in left.items()}
        out.update({q: np.tile(c, n_l) for q, c in right.items()})
        return _distinct_filter(out, *right)

    def _reach_pairs(self, sources, hops, allowed):
        """Packed (a, b) pairs, a in `sources`, b reached from a by a
        directed path of 0..hops edges (b in `allowed` when given)."""
        g = self.graph
        n = g.num_nodes
        indptr, nbr, _ = g.out_csr
        src = np.arange(len(sources))
        node = sources.astype(np.int64)
        seen = np.unique(src * n + node)
        for _ in range(hops):
            counts = indptr[node + 1] - indptr[node]
            total = int(counts.sum())
            self._check(len(seen) + total)
            if total == 0:
                break
            rep_src = np.repeat(src, counts)
            off = np.repeat(indptr[node] - np.concatenate(
                [[0], np.cumsum(counts)[:-1]]), counts)
            nxt = nbr[np.arange(total) + off].astype(np.int64)
            new = np.setdiff1d(np.unique(rep_src * n + nxt), seen,
                               assume_unique=True)
            if len(new) == 0:
                break
            seen = np.union1d(seen, new)
            src, node = new // n, new % n
        a = sources.astype(np.int64)[seen // n]
        b = seen % n
        if allowed is not None:
            keep = allowed[b]
            a, b = a[keep], b[keep]
        return np.unique(a * n + b)


def _pack(a, b, n):
    return a.astype(np.int64) * n + b.astype(np.int64)


def _distinct_filter(tab, *new):
    """Drop rows where a node in `new` takes the graph node of another."""
    keep = None
    for q in new:
        for r, c in tab.items():
            if r == q:
                continue
            m = tab[q] != c
            keep = m if keep is None else keep & m
    if keep is None:
        return tab
    return {q: c[keep] for q, c in tab.items()}
