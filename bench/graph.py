"""The benchmark's own graph over string triples.

Built from the same triples the program ingests, with nothing taken from
the program.  Node ids are the ranks of the sorted labels, so a prefix
keyword is one id interval; `out_csr`/`in_csr` hold each node's edges
sorted by (node, neighbour).  The template sampler (`bench.queries`) and
the reference matcher (`bench.reference`) read it.
"""
from __future__ import annotations

from functools import cached_property

import numpy as np

RESOURCE = 0
LITERAL = 1


def _csr(num_nodes: int, key: np.ndarray, nbr: np.ndarray, pred: np.ndarray):
    order = np.lexsort((nbr, key))
    key, nbr, pred = key[order], nbr[order], pred[order]
    indptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.add.at(indptr, key + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, nbr.astype(np.int32), pred.astype(np.int32)


class Graph:
    def __init__(self, triples, literal_objects=()):
        subs = np.asarray([t[0] for t in triples])
        preds = np.asarray([t[1] for t in triples])
        objs = np.asarray([t[2] for t in triples])
        self.labels, inv = np.unique(np.concatenate([subs, objs]),
                                     return_inverse=True)
        self.src = inv[: len(triples)].astype(np.int32)
        self.dst = inv[len(triples):].astype(np.int32)
        self.predicates, pinv = np.unique(preds, return_inverse=True)
        self.pred = pinv.astype(np.int32)
        # a node is a resource iff it is ever a subject, unless forced
        self.node_kind = np.full(len(self.labels), LITERAL, dtype=np.int8)
        self.node_kind[self.src] = RESOURCE
        if literal_objects:
            forced = np.isin(self.labels,
                             np.asarray(sorted(literal_objects)))
            self.node_kind[forced] = LITERAL

    @property
    def num_nodes(self) -> int:
        return int(self.labels.shape[0])

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])

    @cached_property
    def out_csr(self):
        return _csr(self.num_nodes, self.src, self.dst, self.pred)

    @cached_property
    def in_csr(self):
        return _csr(self.num_nodes, self.dst, self.src, self.pred)

    def interval(self, prefix: str) -> tuple[int, int]:
        """Half-open id interval of the labels that start with `prefix`
        ('' matches every label)."""
        if prefix == "":
            return 0, self.num_nodes
        lo = int(np.searchsorted(self.labels, prefix, side="left"))
        hi = int(np.searchsorted(self.labels, prefix + "￿",
                                 side="right"))
        return lo, hi

    def cardinality(self, prefix: str) -> int:
        lo, hi = self.interval(prefix)
        return hi - lo


def relabel(triples, type_counts: dict[str, int], rng):
    """Triples with the instance ids of every type permuted.

    "Type/<id>" becomes "Type/<perm[id]>" for a permutation drawn per
    type; literals and "Class/<Type>" stay.  The result is isomorphic to
    the input: the same shapes and sizes everywhere, other node ids.
    Returns (triples, {new label: old label})."""
    mapping = {}
    for name, count in type_counts.items():
        perm = rng.permutation(count)
        for i in range(count):
            mapping[f"{name}/{i:08d}"] = f"{name}/{perm[i]:08d}"
    out = [(mapping.get(s, s), p, mapping.get(o, o)) for s, p, o in triples]
    return out, {v: k for k, v in mapping.items()}
