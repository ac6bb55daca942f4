"""The readers of the program's planner and optimizer spans on a synthetic
run: the value per request where the cell's spans are there, nothing for
the other mode or where the program records no such span."""
import pytest

from bench import harness

PACK, UNPACK = "planner_pack_ms.sim", "planner_unpack_ms.sim"
LOWER, COMPILE = "sweep_lower_s.analyze", "sweep_compile_s.analyze"


def _span(name, start, duration, **labels):
    return {"name": name, "labels": labels, "start": start,
            "duration": duration}


def _run(mode, spans, requests=2):
    cell = harness.Cell(name="t", chips=1, config={}, traffic={},
                        metrics=[])
    return harness.Run(cell=cell, mode=mode, setup_s=1.0, window_start=0.0,
                       window_end=10.0, spans=spans,
                       requests=[harness.Request(i, i, i + 1.0, {})
                                 for i in range(requests)])


def _sim_spans():
    out = []
    for t in (0.0, 1.0):
        out += [_span("suite.run", t, 0.9, mode="simulate"),
                _span("suite.resolve", t + 0.01, 0.01),
                _span("suite.pack", t + 0.1, 0.2, mode="simulate"),
                _span("suite.dispatch", t + 0.3, 0.3, mode="simulate"),
                _span("suite.unpack", t + 0.6, 0.25, mode="simulate")]
    return out


def _analyze_spans():
    out = []
    for t in (0.0, 1.0):
        out += [_span("suite.resolve", t, 0.5),
                _span("optimize.lower", t + 0.01, 0.125),
                _span("optimize.compile", t + 0.2, 0.25),
                _span("optimize.run", t + 0.45, 0.04),
                _span("suite.run", t + 0.6, 0.3, mode="analyze"),
                _span("suite.pack", t + 0.62, 0.01, mode="analyze"),
                _span("suite.unpack", t + 0.7, 0.01, mode="analyze")]
    return out


@pytest.mark.parametrize("name,want", [(PACK, 200.0), (UNPACK, 250.0)])
def test_planner_readers_average_over_suite_runs(name, want):
    read = harness.load_reader(name)
    assert read(_run("simulate", _sim_spans())) == pytest.approx(want)
    # analyze's pack and unpack spans are not the simulate planner's
    assert read(_run("simulate", _analyze_spans())) is None
    assert read(_run("analyze", _sim_spans())) is None
    assert read(_run("simulate", [])) is None


def test_planner_phases_fit_inside_the_planner_host_time():
    run = _run("simulate", _sim_spans())
    host = harness.load_reader("suite_host_ms.sim")(run)
    phases = harness.load_reader(PACK)(run) + harness.load_reader(UNPACK)(run)
    assert phases == pytest.approx(450.0) and phases <= host


@pytest.mark.parametrize("name,want", [(LOWER, 0.125), (COMPILE, 0.25)])
def test_sweep_readers_average_over_requests(name, want):
    read = harness.load_reader(name)
    assert read(_run("analyze", _analyze_spans())) == pytest.approx(want)
    assert read(_run("analyze", _analyze_spans(), requests=4)) == \
        pytest.approx(want / 2)
    assert read(_run("simulate", _analyze_spans())) is None
    assert read(_run("analyze", _sim_spans())) is None
    assert read(_run("analyze", [], requests=0)) is None
