"""The trace reduction on a small recorded trace with known answers."""
import json
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")


@pytest.fixture
def events():
    with open(DATA) as f:
        return [tr.Event(*e) for e in json.load(f)["events"]]


def test_window_ends_with_the_last_covered_request(events):
    assert tr.covered_window(events) == (1000, 11000, 2)
    assert tr.covered_window(events, ["lanes"]) == (1000, 11000, 2)


def test_a_request_without_a_named_program_is_not_counted(events):
    no_lanes = [e for e in events if e.name != "jit_lanes(2)"]
    assert tr.covered_window(no_lanes, ["lanes"]) == (1000, 6000, 1)
    assert tr.covered_window(no_lanes) == (1000, 11000, 2)


def test_the_last_request_counts_only_with_a_program_after_it(events):
    cut = [e for e in events if e.name != "jit_squeeze(4)"]
    assert tr.covered_window(cut) == (1000, 6000, 1)


def test_busy_and_idle(events):
    s = tr.summarize(events)
    assert s.window_s == pytest.approx(10000e-9)
    # program runs [2000, 5000] and [8500, 10500]; op lines are not read
    assert s.busy_s == pytest.approx(5000e-9)
    assert s.idle_share == pytest.approx(0.5)
    assert (s.devices, s.requests) == (1, 2)


def test_idle_gaps_named_by_innermost_host_span(events):
    s = tr.summarize(events)
    assert [g[0] for g in s.idle_gaps] == [
        "bench.resolve", "bench.suite_run", "bench.request"]
    assert [g[1] for g in s.idle_gaps] == pytest.approx(
        [3500e-9, 1000e-9, 500e-9])


def test_program_time(events):
    s = tr.summarize(events)
    assert s.program_s == pytest.approx({"lanes": 4500e-9,
                                         "squeeze": 500e-9})
    assert [p[0] for p in s.top_programs] == ["lanes", "squeeze"]


def test_program_time_by_host_span(events):
    s = tr.summarize(events)
    assert s.span_s == pytest.approx({"bench.suite_run": 3000e-9,
                                      "bench.request": 2000e-9})


def test_a_complete_trace_keeps_its_whole_window(events):
    done = events + [
        tr.Event("/device:TPU:0", tr.MODULES_LINE, "jit_lanes(5)", 12000,
                 1000),
        tr.Event("/device:TPU:0", tr.MODULES_LINE, "jit_lanes(6)", 16500,
                 1000)]
    s = tr.summarize(done, ["lanes"])
    assert (s.window_s, s.requests) == (pytest.approx(15000e-9), 3)
    assert s.busy_s == pytest.approx(6200e-9)


def test_a_trace_without_the_window_is_refused(events):
    with pytest.raises(ValueError):
        tr.summarize([e for e in events if e.name != tr.WINDOW])


def test_a_trace_that_lost_the_first_request_is_refused(events):
    late = [e for e in events if not (e.program_run and e.start_ns < 8000)]
    with pytest.raises(ValueError):
        tr.summarize(late)


def test_a_host_only_trace_has_no_device(events):
    s = tr.summarize([e for e in events if not e.on_device])
    assert (s.devices, s.busy_s, s.requests) == (0, 0.0, 0)


def test_program_names():
    assert tr.program_name("jit_lanes(123)") == "lanes"
    assert tr.program_name("jit_analyze_lanes") == "analyze_lanes"
    assert tr.program_name("jit__lambda_") == "_lambda_"
