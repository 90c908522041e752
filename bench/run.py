"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (`src/repro`).
The cell's configuration and traffic mix are found through
`BENCHMARK.json`.  With `--trace 0` the result line carries the cell's
end-to-end metrics; with `--trace 1`, a run of its own under the JAX
profiler, its per-layer metrics.  Earlier lines (on standard output and
standard error) report the data, the pool, the reference's time, the
generator's lateness and the trace; the last lines of standard error are
the numbers compared, each beside its limit; the last line of standard
output is the result as one JSON object.

Exits non-zero with no result when JAX finds no TPU, fewer chips than the
cell asks for, or a device missing from `bench/peaks.json`.  JAX's
persistent compilation cache is kept at `<checkout>/.jax_cache`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def process_age() -> float:
    """Seconds this process had run when the module started, from
    /proc (0 where that cannot be read)."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        uptime = float(Path("/proc/uptime").read_text().split()[0])
    except (OSError, ValueError, IndexError):
        return 0.0
    return max(uptime - start - (time.perf_counter() - T_START), 0.0)


def boot(chips: int) -> dict:
    """Set up paths and the compile cache, and check the device; returns
    the device's entry of `bench/peaks.json`.  Raises SystemExit with a
    message when the checkout or the device will not do."""
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench: no program under {ROOT / 'src'}")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json")
    return peaks["devices"][kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    age0 = process_age()
    sys.path.insert(0, str(ROOT))
    from bench.traffic import load_cell
    cell = load_cell(args.workload)
    peaks = boot(cell.chips)

    from bench.harness import run_cell
    from bench.system import Program
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    Program(cell.chips), t_start=T_START, age0=age0,
                    peaks=peaks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
