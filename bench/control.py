"""The control and the planted faults of the comparison that decides
`correct`.

`RefSystem` puts the benchmark's own reference in the program's place,
behind the same `submit` -> `result()` surface, and can break it in one
of these ways:

  incomplete  the control: each answer keeps only its first half of the
              rows, unflagged (breaks the configuration's "complete"
              guarantee, as a row guard cut early would);
  altered     one node of one row of each answer is replaced where the
              answer is produced;
  half_batch  every second request the server takes is left out of its
              batch, its future failed (a flush holds a single request at
              low load, so the halves are counted across flushes);
  none        sound (the comparison must then hold).

Each must come out not correct (`none` correct).  The tests run them all
at a small size; on the chip, the control at a cell's own size:

    python3 -m bench.control --workload lubm.lookup-zipf --seeds 11,12,13 --seconds 10
"""
from __future__ import annotations

import argparse
import sys
import time
from types import SimpleNamespace


from .graph import Graph
from .reference import Reference

FAULTS = ("none", "incomplete", "altered", "half_batch")


class _Future:
    def __init__(self, server, tpl):
        self.server, self.tpl = server, tpl
        self.value = self.error = None
        self.done = False

    def result(self):
        if not self.done:
            self.server.flush()
        if self.error is not None:
            raise self.error
        return self.value


class _Server:
    def __init__(self, graph: Graph):
        self.ref = Reference(graph, max_rows=1 << 30,
                             max_intermediate=1 << 30)
        self.fault = "none"
        self.pending = []
        self.executions = 0
        self.taken = 0

    def submit(self, tpl):
        f = _Future(self, tpl)
        self.pending.append(f)
        return f

    def flush(self):
        pending, self.pending = self.pending, []
        for f in pending:
            f.done = True
            self.taken += 1
            if self.fault == "half_batch" and self.taken % 2 == 0:
                f.error = RuntimeError("left out of its batch")
                continue
            self.executions += 1
            rows = self.ref.match(f.tpl)
            if self.fault == "incomplete":
                rows = rows[: len(rows) // 2]
            elif self.fault == "altered" and len(rows):
                rows = rows.copy()
                rows[0, 0] = (rows[0, 0] + 1) % self.ref.graph.num_nodes
            n_q = len(f.tpl.keywords)
            f.value = SimpleNamespace(
                rows=rows, cols=tuple(range(n_q)),
                stats=SimpleNamespace(
                    truncated=False, used_check=False, cache_hit=False,
                    candidates_before=0, candidates_after=0,
                    n_estimated_joins=0, join_est_log_err=0.0,
                    conn_reach_pairs=0))


class RefSystem:
    """The `bench.system.Program` surface over the reference."""

    def __init__(self, fault: str = "none"):
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r} not in {FAULTS}")
        self.fault = fault
        self.servers = []

    def load(self, triples, literals):
        return Graph(triples, literals)

    def labels(self, graph):
        return graph.labels

    def query(self, graph, tpl):
        return tpl

    def server(self, graph, trace, **options):
        self.servers.append(_Server(graph))
        return self.servers[-1]

    def executions(self, server):
        return server.executions

    def spans(self, server, t0, t1):
        return []

    def window_start(self):
        # the fault is planted in the timed path, once warm-up is over
        for server in self.servers:
            server.fault = self.fault

    def compiles(self):
        return 0

    def device_info(self):
        return {"platform": "cpu", "kind": "reference", "count": 1}

    def memory_peak_bytes(self):
        return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", default="incomplete",
                    help=f"comma-separated, of {', '.join(FAULTS)}")
    args = ap.parse_args()
    from .harness import log, run_cell
    from .traffic import load_cell
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in args.faults.split(","):
            line = run_cell(cell, seed, args.seconds, False,
                            RefSystem(fault), t_start=time.perf_counter())
            log(f"control {cell.name} seed={seed} fault={fault} "
                f"correct={line['correct']} checks={line['checks']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
