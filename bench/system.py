"""The system under test as the harness sees it: the program's
`Dataset` and `QueryServer`, and JAX for the device, the profiler and
the count of compilations.

Everything the benchmark takes from the program passes through here:
the load path (`Dataset.from_triples`), the served path
(`QueryServer.submit` -> `ResultFuture.result()`, impl "auto", governor
off, result cache off, calibration on unless the traffic file's server
settings turn it off), the node labels that name its answers, its
telemetry and, in a traced run, its spans.
"""
from __future__ import annotations

import jax

from repro.core import Dataset
from repro.core.query import ConnectionEdge, QueryEdge, QueryTemplate
from repro.obs.trace import Tracer
from repro.serve import QueryServer

# JAX's monitoring events: a lowering is a program this process did not
# have (each is then compiled or read from the persistent cache)
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Program:
    def __init__(self, chips: int):
        self.devices = jax.devices()[:chips]
        self._lowered = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == _LOWER:
            self._lowered += 1

    # ---- data and queries -------------------------------------------
    def load(self, triples, literals):
        return Dataset.from_triples(triples, literal_objects=literals)

    def labels(self, dataset):
        return dataset.graph.labels

    def query(self, dataset, tpl) -> QueryTemplate:
        g = dataset.graph
        return QueryTemplate(
            keywords=list(tpl.keywords),
            edges=[QueryEdge(s, d, g.predicate_id(p))
                   for s, d, p in tpl.edges],
            connections=[ConnectionEdge(s, d, h)
                         for s, d, h in tpl.connections])

    def server(self, dataset, trace: bool, **options) -> QueryServer:
        """`options` are the traffic file's server settings."""
        options = {"calibrate": True, **options}
        return QueryServer(dataset, impl="auto",
                           tracer=Tracer(max_traces=1 << 20)
                           if trace else None, **options)

    # ---- counters ------------------------------------------------------
    def executions(self, server) -> int:
        return int(server.telemetry()["batch"]["executions"])

    def spans(self, server, t0: float, t1: float) -> list:
        if not server.tracer.enabled:
            return []
        return [s for tr in server.tracer.finished for s in tr.spans
                if t0 <= s.start <= t1]

    def window_start(self) -> None:
        """The measured window opens: count compilations from here."""
        self._lowered = 0

    def compiles(self) -> int:
        return self._lowered

    # ---- device and profiler ---------------------------------------------
    def device_info(self) -> dict:
        d = self.devices[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}

    def memory_peak_bytes(self) -> int | None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def start_profile(self, directory: str):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(directory, profiler_options=opts)
        return jax.profiler.TraceAnnotation

    def stop_profile(self) -> None:
        jax.profiler.stop_trace()
