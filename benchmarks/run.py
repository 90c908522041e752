"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  REPRO_BENCH_SCALE (default 0.12)
sizes the synthetic datasets; REPRO_BENCH_QUERIES the workload size.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (table1_metrics, fig3_index_space, fig4_query_datasets,
                   fig5_dataset_scaling, fig6_template_scaling,
                   sec63_connection_edges, kernel_micro, join_micro,
                   query_micro, connection_micro, serve_micro,
                   robust_micro, obs_micro, update_micro)
    modules = [table1_metrics, fig3_index_space, fig4_query_datasets,
               fig5_dataset_scaling, fig6_template_scaling,
               sec63_connection_edges, kernel_micro, join_micro,
               query_micro, connection_micro, serve_micro,
               robust_micro, obs_micro, update_micro]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failed = []
    for mod in modules:
        short = mod.__name__.split(".")[-1]
        if only and only not in short:
            continue
        t0 = time.time()
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:                               # noqa: BLE001
            print(f"{short}.ERROR,0,{e!r}", flush=True)
            failed.append(short)
        print(f"# {short} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
