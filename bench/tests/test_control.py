"""The controls come out as not correct under the limits of the cells.

The control is the plain reference put in the program's place, computed
one precision below the configuration's float64: a float32 clock for the
event dynamics (client by client, or class by class), float32 closed
forms and search for ``time_opt``.  The runs are at the cells' own sizes;
only the lanes and concurrencies the check would draw are fewer.  (On the
chip the analyze cell's control is the program's own float32 Buzen
kernel; its readings are in PERF.md.)
"""
import json
import os

import numpy as np
import pytest

from bench import fleet, harness
from bench.modes.analyze import LEAVES
from bench.modes.simulate import compare, leaf_gap, reference
from bench.reference import closed_forms


def _cell(name):
    cell = harness.load_cell(name)
    return cell.config, cell.traffic, cell.traffic["check"]["limits"]


@pytest.mark.parametrize("seed", [3, 2**31 + 7, 987654321])
@pytest.mark.parametrize("workload", ["table1.sim-msweep",
                                      "class1m.sim-m1000"])
def test_float32_clock_fails_the_simulate_limits(workload, seed):
    config, t, limits = _cell(workload)
    replay, arrays = reference(config)
    args = (arrays, fleet.uniform_routing(config), max(t["concurrency"]),
            t["m_max"], seed, t["warmup"], t["updates"])
    mismatch, gap = compare(replay(*args, dtype=np.float32), replay(*args))
    assert mismatch > limits["count_mismatch"] or gap > limits["stats_gap"]


def test_float32_closed_forms_and_search_fail_the_analyze_limits():
    config, t, limits = _cell("table1.analyze-timeopt")
    arrays, consts = fleet.arrays(config), config["learning_constants"]
    ms = [32, 33, 34]  # around the optimum, m = 33
    args = (arrays, ms, consts, t["m_max"], t["steps"])
    _, taus = closed_forms.time_opt(*args)
    p32, taus32 = closed_forms.time_opt(*args, dtype=np.float32)
    b = int(np.argmin(taus32))
    control = closed_forms.closed_forms(arrays, p32[b], ms[b], consts,
                                        t["m_max"], dtype=np.float32)
    ref = closed_forms.closed_forms(arrays, p32[b], ms[b], consts,
                                    t["m_max"])
    closed_form_gap = max(leaf_gap(control[k], ref[k]) for k in LEAVES)
    opt_gap = abs(float(taus32[b]) - float(taus[b])) / float(taus[b])
    assert (closed_form_gap > limits["closed_form_gap"]
            or opt_gap > limits["opt_gap"])


def test_limits_sit_between_the_readings():
    """Each limit lies above the largest sound reading and below the
    smallest control reading measured on the chip (PERF.md, §4)."""
    readings = {  # (largest sound, smallest control) on a TPU v5e
        "sim-msweep": {"stats_gap": (2.9e-11, 0.38)},
        "sim-m1000": {"stats_gap": (2.6e-13, 0.018)},
        "analyze-timeopt": {"closed_form_gap": (2.3e-8, 2.5e-4),
                            "opt_gap": (1.6e-8, 1.6e-4)},
    }
    for traffic, numbers in readings.items():
        with open(os.path.join(harness.ROOT, "bench", "traffic",
                               traffic + ".json")) as f:
            limits = json.load(f)["check"]["limits"]
        for name, (low, high) in numbers.items():
            assert 10 * low < limits[name] < high / 3, (traffic, name)
