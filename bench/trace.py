"""Reduction of a profiler trace to the numbers per-layer metrics read.

`from_xplane` turns the profiler's `.xplane.pb` into a plain form -
planes of lines of [name, start_ns, duration_ns] events - and `reduce`
works on that form only, so a small recorded trace can be checked
without a chip. Device planes are `/device:TPU:<i>`; on each, the
"XLA Ops" line holds the operations and the "XLA Modules" line holds one
event per execution of a compiled program (named `jit_<function>(<id>)`).
Host spans are the `jax.profiler.TraceAnnotation`s the drivers open; the
one named "window" marks the traced stretch.
"""
from __future__ import annotations

import re
import time

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
_SUFFIX = re.compile(r"\(\d+\)$")


def from_xplane(path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for pl in data.planes:
        lines = [{"name": ln.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in ln.events]}
                 for ln in pl.lines]
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """Each event's duration less that of the events nested inside it on
    the same line (a loop op spans its body's ops)."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    self_t = [e[2] for e in evs]
    stack = []  # indices of open events
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= d
        stack.append(i)
    return [(evs[i][0], max(t, 0.0)) for i, t in enumerate(self_t)]


def reduce(trace: dict, host_spans=("submit", "step", "prefill", "decode",
                                    "train_step", "sync", "wait")) -> dict:
    """busy_s (union of op intervals, averaged over device planes),
    window_s, programs {name: [seconds per execution]}, top device ops by
    self time, and the longest idle gaps named by the innermost host span
    open at their midpoint ("host" where none is)."""
    devices = [p for p in trace["planes"]
               if p["name"].startswith("/device:TPU:")]
    host = [e for p in trace["planes"] if not p["name"].startswith("/device")
            for ln in p["lines"] for e in ln["events"]]
    win = [e for e in host if e[0] == WINDOW_SPAN]
    if win:
        w0, w1 = win[0][1], win[0][1] + win[0][2]
    else:
        starts = [e[1] for p in devices for ln in p["lines"]
                  for e in ln["events"]]
        ends = [e[1] + e[2] for p in devices for ln in p["lines"]
                for e in ln["events"]]
        if not starts:
            return {"devices": 0}
        w0, w1 = min(starts), max(ends)
    spans = sorted((e for e in host if e[0] in host_spans),
                   key=lambda e: -e[1])  # innermost = latest start first

    busy, programs, ops, gaps = [], {}, {}, []
    for p in devices:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        op_events = [e for e in lines.get(OPS_LINE, [])
                     if e[1] < w1 and e[1] + e[2] > w0]
        merged = _union([(max(e[1], w0), min(e[1] + e[2], w1))
                         for e in op_events])
        busy.append(sum(e - s for s, e in merged))
        for name, t in _self_times(op_events):
            name = name.split(" = ")[0]  # the HLO instruction, not its text
            ops[name] = ops.get(name, 0.0) + t * 1e-9
        for e in lines.get(MODULES_LINE, []):
            if w0 <= e[1] and e[1] + e[2] <= w1:
                programs.setdefault(program_name(e[0]), []).append(e[2] * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = sorted(((e - s, s) for s, e in zip(edges[::2], edges[1::2])
                       if e > s), reverse=True)[:10]
        for dur, s in idle:
            mid = s + dur / 2
            label = next((h[0] for h in spans
                          if h[1] <= mid < h[1] + h[2]), "host")
            gaps.append([label, dur * 1e-9])
    n = max(len(devices), 1)
    return {
        "devices": len(devices),
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) * 1e-9 / n,
        "programs": programs,
        "device_ops": sorted(([k, v / n] for k, v in ops.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(gaps, key=lambda x: -x[1])[:10],
    }


class Stretch:
    """A profiler capture of one stretch of the measured window, marked by
    a host span named "window". Drivers call `poll(now)` as they go.
    `began` and `ended` are the `time.perf_counter()` readings before the
    profiler starts and after it has stopped: the part of the window that
    rates outside the stretch leave out, the profiler's own stalls with it."""

    def __init__(self, directory, start_at: float, length_s: float):
        self.directory = str(directory)
        self.start_at, self.end_at = start_at, start_at + length_s
        self.active = self.done = False
        self.began = self.ended = None
        self._span = None

    def poll(self, now: float) -> None:
        import jax

        if not self.active and not self.done and now >= self.start_at:
            self.began = time.perf_counter()
            jax.profiler.start_trace(self.directory)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
            self.active = True
        elif self.active and now >= self.end_at:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.active:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.ended = time.perf_counter()
            self.active, self.done = False, True

    def outside(self, t0: float, t1: float) -> list:
        """The parts of [t0, t1) outside [began, ended]."""
        if self.began is None:
            return [(t0, t1)]
        end = self.ended if self.ended is not None else t1
        return [(a, b) for a, b in ((t0, min(t1, self.began)),
                                    (max(t0, end), t1)) if b > a]

    def read(self) -> dict:
        """The plain form of the captured trace ({} when none was taken);
        the profiler's files are removed."""
        import pathlib
        import shutil

        files = sorted(pathlib.Path(self.directory).rglob("*.xplane.pb"))
        trace = from_xplane(files[-1]) if files else {}
        shutil.rmtree(self.directory, ignore_errors=True)
        return trace
