"""Profiling hooks: device scopes, host spans and trace capture.

Three pieces:

  * `scope(name)` - `jax.named_scope`: applied at trace time inside
    jitted code, it names the enclosed ops in the HLO's op metadata and
    so in profiler timelines. It changes nothing else in the compiled
    program. The model step names its parts with it (`repro.kv_write`,
    `repro.kv_gather`, `repro.attn_core`, `repro.hadamard_adapter`,
    `repro.mlp`, `repro.lm_head`), and the Pallas dispatches in
    `repro.kernels.ops` name theirs.
  * `annotate(name)` / `span(counters, name)` - host regions on the
    profiler's clock (`jax.profiler.TraceAnnotation`, a no-op unless a
    capture is running). A `span` also adds its seconds and one call to
    its phase's `phase_counters` (`serve_phase_seconds_total` /
    `serve_phase_calls_total{sched, phase}`), so the serving tick's
    phases are counted in every run, traced or not.
  * `ProfiledTicks` - capture: the `launch/serve --profile-dir` hook.
    Starts `jax.profiler.start_trace` now and stops after N scheduler
    ticks, tolerating a serve that drains earlier.
"""
from __future__ import annotations

import time
import warnings

import jax


def scope(name: str):
    """Trace-time scope: names enclosed ops in HLO/profiles. Usable both
    as a context manager and (via jax.named_scope semantics) a decorator."""
    return jax.named_scope(name)


def annotate(name: str, **attrs):
    """Host-side profiler region (no-op outside a capture); `attrs` come
    back as the trace event's stats."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def phase_counters(registry, sched: str, phase: str):
    """The (seconds, calls) counter pair of one host phase:
    `serve_phase_seconds_total` and `serve_phase_calls_total{sched,
    phase}`. With a disabled registry both are its shared no-ops."""
    return (registry.counter("serve_phase_seconds_total",
                             sched=sched, phase=phase),
            registry.counter("serve_phase_calls_total",
                             sched=sched, phase=phase))


class span:
    """A host phase on the profiler's clock and in the registry:

        emit = phase_counters(obs, "paged", "emit")  # once, at set-up
        with span(emit, "serve.emit"):
            ...

    opens `annotate(name, **attrs)` and, on exit, adds the seconds spent
    and one call to the phase's (seconds, calls) counters. `seconds`
    holds the reading once the span closed; `set_metadata(**attrs)` adds
    attrs known only after the work ran."""

    __slots__ = ("_ann", "_seconds", "_calls", "_t0", "seconds")

    def __init__(self, counters, name: str, **attrs):
        self._seconds, self._calls = counters
        self._ann = annotate(name, **attrs)
        self.seconds = 0.0

    def set_metadata(self, **attrs) -> None:
        self._ann.set_metadata(**attrs)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._seconds.inc(self.seconds)
        self._calls.inc()
        self._ann.__exit__(*exc)


class ProfiledTicks:
    """Capture a profiler trace spanning the next `n` scheduler ticks.

    Usage (launch/serve --profile-dir):

        prof = ProfiledTicks(log_dir, n=8)
        while driving:
            sched.step()
            prof.tick()
        prof.stop()  # idempotent; stops early if the serve drained first
    """

    def __init__(self, log_dir: str, n: int = 8):
        self.log_dir = log_dir
        self.remaining = max(1, int(n))
        self._started = False
        self._stopped = False
        try:
            jax.profiler.start_trace(log_dir)
            self._started = True
        except Exception as e:  # pragma: no cover - profiler-less build
            warnings.warn(f"profiler trace not started: {e}")
            self._stopped = True

    def tick(self) -> None:
        """Count one scheduler tick; stops the capture at zero."""
        if self._stopped:
            return
        self.remaining -= 1
        if self.remaining <= 0:
            self.stop()

    def stop(self) -> None:
        if self._started and not self._stopped:
            jax.profiler.stop_trace()
        self._stopped = True
