"""The benchmark's harness: finds a cell's files by name, times its window,
reads its metrics and decides ``correct``.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``   — the configuration (sizes, source);
* ``bench/traffic/<traffic>.json``  — the traffic mix; its ``mode`` key
  names the generator ``bench/modes/<mode>.py`` that reads it;
* ``bench/metrics/<metric>.py``     — one reader per metric, a function
  ``read(run) -> float | None`` over the :class:`Run` record.

A new cell therefore needs new files and a new ``BENCHMARK.json`` entry,
and no edit of any file here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
import traceback
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list        # [(name, unit, source, kind)] this cell reports
    root: str = ROOT


@dataclasses.dataclass
class Request:
    index: int
    start: float         # host clock (time.perf_counter)
    end: float
    work: dict           # mode-specific counts (updates, lanes, ...)
    output: object = None
    error: Optional[str] = None


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: Cell
    mode: str
    setup_s: float
    window_start: float
    window_end: float
    requests: list
    traces: int = 0           # jaxpr traces inside the window
    spans: list = dataclasses.field(default_factory=list)   # suite spans
    trace: object = None      # bench.trace.Summary of a --trace 1 run

    @property
    def done(self) -> list:
        return [r for r in self.requests if r.error is None]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration, traffic and the
    metrics that ``BENCHMARK.json`` has it report."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    config = _load_json(os.path.join(root, "bench", "configs",
                                     w["config"] + ".json"))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench.get(kind, []):
            if workload in m.get("workloads", [workload]):
                metrics.append((m["name"], m["unit"], m["source"], kind))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, metrics=metrics, root=root)


def load_mode(cell: Cell):
    return _module(os.path.join(cell.root, "bench", "modes",
                                cell.traffic["mode"] + ".py"),
                   "bench_mode_" + cell.traffic["mode"])


def load_reader(name: str, root: str = ROOT):
    """``read(run)`` of the metric ``name`` (``bench/metrics/<name>.py``)."""
    mod = _module(os.path.join(root, "bench", "metrics", name + ".py"),
                  "bench_metric_" + name.replace(".", "_").replace("-", "_"))
    return mod.read


def read_metrics(cell: Cell, run: Run, kind: str) -> dict:
    out = {}
    for name, unit, _source, k in cell.metrics:
        if k != kind:
            continue
        value = load_reader(name, cell.root)(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class NoChip(RuntimeError):
    pass


def _devices(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator (its devices are "
                     f"{devices[0].platform}); the benchmark runs only on "
                     "one")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:chips]


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # bench.* annotations only, no calls
    opts.host_tracer_level = 1
    return opts


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True, out=None,
        err=None) -> tuple:
    """One run of ``cell``: set-up, a window of ``seconds``, the metrics,
    the correctness comparison.  Returns ``(exit code, result or None)``
    and prints the result as the last line of ``out``."""
    out = out or sys.stdout
    err = err or sys.stderr
    src = os.path.join(cell.root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no system under test at {src}", file=err)
        return 2, None
    # the compile cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cell.root,
                                                           ".jax_cache")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        devices = _devices(cell.chips, require_chip)
    except NoChip as e:
        print(f"bench: {e}", file=err)
        return 1, None

    import jax
    from repro.analysis import tracecheck
    from repro.serve.xla_cache import enable_persistent_cache

    enable_persistent_cache()
    mode = load_mode(cell).Mode(cell, seed)
    with jax.profiler.TraceAnnotation("bench.setup"):
        mode.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    # the profiler records every operation of every loop iteration: a
    # traced window holds the traffic's first few requests, so that the
    # trace stays whole and is read well inside a run's time, and one
    # request more, whose programs show that the last one was recorded
    # to its end
    trace_requests = int(cell.traffic.get("trace_requests", 1)) + 1
    requests = []
    with tracecheck.watch() as w:
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profiler_options())
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            i = 0
            while True:
                rs = time.perf_counter()
                work, output, error = {}, None, None
                try:
                    with jax.profiler.TraceAnnotation("bench.request"):
                        work, output = mode.request(i)
                except Exception:  # noqa: BLE001 — counted, reported, fails
                    error = traceback.format_exc(limit=8)
                    print(f"bench: request {i} failed:\n{error}", file=err)
                re_ = time.perf_counter()
                requests.append(Request(i, rs, re_, work, output, error))
                i += 1
                if re_ - t0 >= seconds or (trace and i >= trace_requests):
                    break
            t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
    fresh = w.compiles - w.cache_hits
    print(f"bench: window {t1 - t0:.3f} s, {len(requests)} requests, "
          f"compiles in the window: {fresh} (expected 0), persistent-cache "
          f"loads {w.cache_hits}, jaxpr traces {w.traces}", file=err)

    summary = None
    if trace:
        import shutil

        from bench import trace as tr

        try:
            summary = tr.summarize(tr.load_events(trace_dir),
                                   cell.traffic.get("trace_programs"))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"bench: traced requests counted {summary.requests}; device "
              "seconds by program: " + json.dumps(summary.program_s)
              + "; by host span: " + json.dumps(summary.span_s), file=err)

    memory_peak = _memory_peak(devices)
    record = Run(cell=cell, mode=cell.traffic["mode"], setup_s=setup_s,
                 window_start=t0, window_end=t1, requests=requests,
                 traces=w.traces, trace=summary)
    mode.annotate(record)
    done = record.done
    if done:
        def mean_span(name):
            xs = [x["duration"] for x in record.spans if x["name"] == name]
            return sum(xs) / len(done) if xs else 0.0

        print(f"bench: per request: wall "
              f"{sum(r.end - r.start for r in done) / len(done):.4f} s, "
              f"suite.run {mean_span('suite.run'):.4f} s, suite.dispatch "
              f"{mean_span('suite.dispatch'):.4f} s", file=err)
    metrics = read_metrics(cell, record,
                           "per_layer" if trace else "end_to_end")
    checks = mode.check(record) if record.done else []
    failed = sum(r.error is not None for r in requests)
    checks.append(("failed_requests", float(failed), 0.0))
    correct = all(v <= lim for _, v, lim in checks)

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(requests),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_programs,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    for name, v, lim in checks:
        print(f"check {name}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0, result
