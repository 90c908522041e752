"""The comparison that decides `correct` fails the control and every
planted fault, and passes the sound reference, in the benchmark's cell
at a small size, under open-loop and closed-loop arrivals (the chip runs
the control at the cell's own size: `python3 -m bench.control`)."""
import time

import pytest

from bench.control import FAULTS, RefSystem
from bench.harness import run_cell
from bench.traffic import load_cell


def _small(arrivals):
    cell = load_cell("lubm.lookup-zipf")
    cell.config["universities"] = 1
    cell.config["profile"]["departments_per_university"] = [1, 1]
    cell.traffic["templates"]["pool"] = 6
    cell.traffic["arrivals"] = ({"kind": "open", "rate_qps": 20.0}
                                if arrivals == "open" else {"kind": "closed"})
    return cell


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("arrivals", ["open", "closed"])
def test_bench_control_and_faults(arrivals, fault):
    seconds = 0.6 if arrivals == "open" else 0.1
    line = run_cell(_small(arrivals), 9, seconds, False, RefSystem(fault),
                    t_start=time.perf_counter())
    assert line["correct"] is (fault == "none"), line["checks"]
    assert line["attempted"] > 1
    assert list(line)[-1] == "checks"
