"""A configuration, a traffic mix and a per-layer metric join the benchmark
as new files and a new ``BENCHMARK.json`` entry, with no edit of any file
the benchmark already has."""
import hashlib
import json
import os
import shutil

import pytest

from bench import harness


def _digests(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    return str(tmp_path)


def test_new_files_are_found_by_name(root):
    before = _digests(root)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump({"name": "tiny", "fleet": "clusters", "clusters": [
            {"name": "A", "mu_c": 1.0, "mu_u": 2.0, "mu_d": 3.0,
             "count": 4}]}, f)
    with open(os.path.join(root, "bench", "traffic", "burst.json"),
              "w") as f:
        json.dump({"mode": "simulate", "concurrency": [2],
                   "seeds_per_concurrency": 1, "m_max": 2, "warmup": 0,
                   "updates": 10, "backend": "batched"}, f)
    with open(os.path.join(root, "bench", "metrics", "requests_done.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run.done)\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny",
                               "traffic": "burst", "chips": 1,
                               "why": "a test cell"})
    bench["per_layer"].append({"name": "requests_done", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "planner",
                               "moves": "sim_updates_per_s",
                               "workloads": ["tiny.burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.load_cell("tiny.burst", root)
    assert cell.config["clusters"][0]["count"] == 4
    assert cell.traffic["updates"] == 10
    assert ("requests_done", "requests", "host_clock", "per_layer") \
        in cell.metrics
    assert harness.load_mode(cell).Mode.__name__ == "Mode"
    run = harness.Run(cell=cell, mode="simulate", setup_s=1.0,
                      window_start=0.0, window_end=1.0,
                      requests=[harness.Request(0, 0.0, 1.0, {})])
    assert harness.read_metrics(cell, run, "per_layer") == {
        "requests_done": {"value": 1.0, "unit": "requests"}}
    after = _digests(root)
    assert {k: after[k] for k in before} == before  # nothing edited


def test_a_metric_with_nothing_to_read_is_left_out(root):
    cell = harness.load_cell("table1.sim-msweep", root)
    run = harness.Run(cell=cell, mode="simulate", setup_s=1.0,
                      window_start=0.0, window_end=1.0, requests=[])
    out = harness.read_metrics(cell, run, "per_layer")
    assert out == {}  # no trace, no spans: nothing, and never a 0


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.metrics, w["name"]


def test_an_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no.such-cell")
