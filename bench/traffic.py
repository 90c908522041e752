"""Cells, data and traffic, all found by name and made from data files.

A cell of `BENCHMARK.json` names a configuration (`configs[].file`, a
JSON file of the deployment: generator, size, profile, the generator's
own seed, the guarantees) and a traffic mix
(`bench/traffic/<traffic>.json`: the template recipe, popularity and
arrivals).  A configuration is its file plus an optional generator
file: its `generator` key names `bench/generators/<generator>.py`,
which it brings as its own or shares with another configuration, and
whose `generate(config) -> (triples, literal objects, instances per
type)` makes the data.  Adding any of these is adding a file and an
entry; no code here names one.

A generator's data keeps one convention, which `check_generated`
asserts before a run uses it: every instance of a type counted in
"instances per type" is labelled "Type/<8-digit id>", with the ids
0..count-1, and only those types' instances have such labels.

Steadiness.  Every seed gets the same work in another order:

  - the dataset is the configuration's (its generator at its own seed,
    as LUBM's UBA is run at seed 0), and `--seed` permutes the
    instance ids of every type, so the graph each seed serves is
    isomorphic to every other's, with other node ids;
  - templates are sampled from the unpermuted graph with the traffic
    file's `template_seed`, in a fixed slot order (sizes and connection
    edges by slot), and their exact labels are mapped through the
    permutation;
  - arrival gaps are the quantiles of the exponential distribution at
    the file's rate, and the request sequence holds each template as
    often as its popularity says; `--seed` only orders them.

A template enters a pool only when the benchmark's reference gives it a
complete answer of 1 to `max_answer_rows` rows, and no table a join
engine can build for it passes the deployment's row guard
(`Reference.within_guard`), so that the engine can answer it whole.
"""
from __future__ import annotations

import importlib.util
import itertools
import json
import re
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .graph import Graph
from .queries import Template, random_query
from .reference import Reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# candidates per template slot; under 10^3, the seed stride between slots
MAX_ATTEMPTS = 200
# an instance's label: its type, a slash and its zero-padded id
INSTANCE = re.compile(r"([A-Za-z]\w*)/(\d{8})")


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose)."""
    words = [int(seed) % (1 << 63)] + [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    # per-layer metric entries of BENCHMARK.json this cell reports
    per_layer: list
    end_to_end: list
    # the directory that holds its generators and metric readers
    bench_dir: Path


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])

    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                bench_dir=root / "bench")


def make_data(config: dict, bench_dir: Path = BENCH
              ) -> tuple[list, set, dict]:
    """(triples, literal objects, instances per type) of a configuration:
    the `generate` of `<bench_dir>/generators/<generator>.py` over the
    whole configuration (size, profile, seed)."""
    name = config["generator"]
    path = bench_dir / "generators" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {config['name']!r} names "
                                f"the generator {name!r}, but {path} does "
                                f"not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_generator_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate(config)


def check_generated(triples, literals, counts: dict[str, int]) -> None:
    """Raise ValueError where a generator's data breaks the convention
    that `graph.relabel` needs to permute it: every triple three
    strings; each type of `counts` labelled "Type/<8-digit id>" with
    exactly the ids 0..count-1; no such label of a type `counts` does
    not name; the literal objects among the triples' objects.  A label
    `relabel` does not know stays as it is, so a break would serve
    every `--seed` the same graph, with no error."""
    bad = next((t for t in triples
                if len(t) != 3 or not all(isinstance(x, str) for x in t)),
               None)
    if bad is not None:
        raise ValueError(f"triple {bad!r} is not three strings")
    objects = {o for _, _, o in triples}
    ids: dict[str, set] = {}
    for label in objects.union(s for s, _, _ in triples):
        m = INSTANCE.fullmatch(label)
        if m:
            ids.setdefault(m[1], set()).add(int(m[2]))
    stray = sorted(set(ids) - set(counts))
    if stray:
        raise ValueError(f"labels of the form Type/<8-digit id> for types "
                         f"not in the instance counts: {stray}")
    for kind, count in counts.items():
        got, want = ids.get(kind, set()), set(range(count))
        if got != want:
            raise ValueError(
                f"{kind}: the instance counts say {count}, the labels hold "
                f"{len(got)} ids of {kind}/<8 digits>, {len(got - want)} "
                f"of them outside 0..{count - 1}")
    missing = set(literals) - objects
    if missing:
        raise ValueError(f"{len(missing)} literal objects are no triple's "
                         f"object, such as {sorted(missing)[:3]}")


# -------------------------------------------------------------------- #
# Templates
# -------------------------------------------------------------------- #
@dataclass
class Entry:
    """One template of a pool: as sampled (unpermuted labels), as served
    (labels through the seed's permutation), and its reference answer in
    the benchmark graph's node ids."""
    sampled: Template
    served: Template
    answer: np.ndarray


def template_stream(graph: Graph, ref: Reference, recipe: dict,
                    forward: dict):
    """Pool entries, slot by slot.  Slot j has size
    `sizes[j % len(sizes)]` and one connection edge when
    j % connection_every == connection_every - 1; its candidates are
    drawn with seeds template_seed*10^6 + j*10^3 + attempt until the
    reference accepts one."""
    sizes = recipe["sizes"]
    every = int(recipe.get("connection_every", 0))
    for slot in itertools.count():
        conn = int(every > 0 and slot % every == every - 1)
        for attempt in range(MAX_ATTEMPTS):
            tpl = random_query(
                graph, size=int(sizes[slot % len(sizes)]),
                seed=int(recipe["template_seed"]) * 10 ** 6
                + slot * 10 ** 3 + attempt,
                n_connection=conn, d_c=int(recipe["d_c"]),
                exact_nodes=float(recipe["exact_nodes"]))
            if not ref.within_guard(tpl):
                continue
            rows = ref.match(tpl)
            if rows is not None and len(rows) >= 1:
                break
        else:
            raise RuntimeError(f"slot {slot}: no template accepted")
        served = Template([forward.get(k, k) for k in tpl.keywords],
                          list(tpl.edges), list(tpl.connections))
        yield Entry(tpl, served, rows)


# -------------------------------------------------------------------- #
# Arrivals and popularity
# -------------------------------------------------------------------- #
def poisson_dues(rate: float, seconds: float, rng) -> np.ndarray:
    """round(rate*seconds) due times in [0, seconds): the exponential
    gaps' quantiles, scaled to span the window exactly, in an order
    drawn from `rng`."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def shuffled_cycles(n_templates: int, rng):
    """Template indices without end: each block of `n_templates` holds
    every template once, in an order drawn from `rng`."""
    while True:
        yield from rng.permutation(n_templates).tolist()


def zipf_sequence(n_requests: int, n_templates: int, s: float, rng
                  ) -> np.ndarray:
    """Template index per request: index r (rank r+1) appears in
    proportion to 1/(r+1)^s, counts rounded by largest remainder, in an
    order drawn from `rng`."""
    p = 1.0 / np.arange(1, n_templates + 1) ** s
    p /= p.sum()
    want = p * n_requests
    counts = np.floor(want).astype(np.int64)
    short = n_requests - int(counts.sum())
    counts[np.argsort(-(want - counts), kind="stable")[:short]] += 1
    seq = np.repeat(np.arange(n_templates), counts)
    return rng.permutation(seq)
