"""Reduction of a profiler trace to device busy time, idle gaps and
per-program device time.

A trace is read into plain ``Event`` records (plane, line, name, start and
duration in ns); everything after that works on those records, so a small
recorded trace in a test exercises the same arithmetic as a chip run.

* Device events: the ``XLA Modules`` line of each ``/device:`` plane, one
  event per program run.  The TPU's op-level lines record every
  iteration of a ``while`` loop (millions of events per window for the
  event engine), more than a run can read in its time, so they are not
  read.
* Busy: the union of the device events' intervals inside the window, per
  device, averaged over the devices that ran anything.
* Idle gaps: the complement of that union inside the window, each named
  by the innermost host annotation (``bench.*``) that covers its midpoint.
* Program time: the summed durations by program (``jit_lanes(..)`` counts
  under ``lanes``), and by the innermost host annotation in which each run
  starts (``bench.resolve``: the optimizer's programs).
* Coverage: a ``bench.request`` counts where it holds the start of a run
  of one of the traffic's named programs (any program, where it names
  none) and a program run starts after it ends, so that its tail was
  recorded too.  Where the profiler dropped events (its buffer is
  finite), the window ends with the last request of the unbroken run of
  counted ones, and ``requests`` says how many that is.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable

MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
REQUEST = "bench.request"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def on_device(self) -> bool:
        return self.plane.startswith("/device:")

    @property
    def program_run(self) -> bool:
        return self.on_device and self.line == MODULES_LINE


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over the devices that ran
    devices: int
    requests: int                 # requests the window covers in full
    program_s: dict               # program name -> device seconds
    span_s: dict                  # host annotation -> device seconds
    top_programs: list            # [[name, seconds], ...] by device time
    idle_gaps: list               # [[host span, seconds], ...] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load_events(trace_dir: str) -> list:
    """The program runs and ``bench.*`` annotations of the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(HOST_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def program_name(module: str) -> str:
    """``jit_lanes(123)`` / ``jit_lanes`` -> ``lanes``."""
    name = re.sub(r"\(.*$", "", module)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals: Iterable[tuple]) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host(events: list, name: str) -> list:
    return sorted((e for e in events if e.name == name and not e.on_device),
                  key=lambda e: e.start_ns)


def covered_window(events: list, programs=None) -> tuple:
    """``(start_ns, end_ns, requests)`` of the traced window, cut back to
    the unbroken run of requests that each hold a run of one of
    ``programs`` (any program where ``None``) and are followed by one."""
    marks = _host(events, WINDOW)
    if not marks:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w = max(marks, key=lambda e: e.dur_ns)
    runs = [e for e in events if e.program_run]
    if not runs:  # no device planes (a CPU run): nothing to cut back
        return w.start_ns, w.end_ns, 0
    named = [e.start_ns for e in runs
             if programs is None or program_name(e.name) in programs]
    last = max(e.start_ns for e in runs)
    reqs = [r for r in _host(events, REQUEST)
            if w.start_ns <= r.start_ns and r.end_ns <= w.end_ns]
    covered = 0
    for r in reqs:
        if not (any(r.start_ns <= s <= r.end_ns for s in named)
                and last > r.end_ns):
            break
        covered += 1
    if covered == 0:
        raise ValueError("the trace holds the window's first request only "
                         f"in part (named programs: {programs})")
    return w.start_ns, reqs[covered - 1].end_ns, covered


def _innermost(host: list, t: float) -> str:
    cover = [h for h in host if h.start_ns <= t <= h.end_ns]
    return min(cover, key=lambda h: h.dur_ns).name if cover else \
        "no bench span"


def summarize(events: list, programs=None, top: int = 10) -> Summary:
    lo, hi, requests = covered_window(events, programs)
    host = [e for e in events if e.name.startswith(HOST_PREFIX)
            and e.name != WINDOW and not e.on_device]
    per_device: dict = {}
    for e in events:
        if e.program_run:
            per_device.setdefault(e.plane, []).append(e)
    busy, gaps_all = [], []
    by_program: dict = {}
    by_span: dict = {}
    for evs in per_device.values():
        u = _union((max(e.start_ns, lo), min(e.end_ns, hi)) for e in evs
                   if e.end_ns > lo and e.start_ns < hi)
        if not u:
            continue
        busy.append(sum(e - s for s, e in u))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps_all += [(edges[k], edges[k + 1])
                     for k in range(0, len(edges), 2)
                     if edges[k + 1] > edges[k]]
        for e in evs:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                k = program_name(e.name)
                by_program[k] = by_program.get(k, 0.0) + d * 1e-9
                k = _innermost(host, e.start_ns)
                by_span[k] = by_span.get(k, 0.0) + d * 1e-9
    named = [[_innermost(host, 0.5 * (s + e)), (e - s) * 1e-9]
             for s, e in gaps_all]
    named.sort(key=lambda x: -x[1])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=(sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
        devices=len(busy), requests=requests, program_s=by_program,
        span_s=by_span,
        top_programs=sorted(([k, v] for k, v in by_program.items()),
                            key=lambda kv: -kv[1])[:top],
        idle_gaps=named[:top])
