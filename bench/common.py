"""Pieces every driver shares: the device check, the compile counter, the
collector's passes, the peaks table, keys from large seeds, raw-sample
percentiles, and the lookup from a configuration file to its
architecture's module.

The device check and the compile counter follow `chip_smoke.py`'s
`device_info` and `CompileClock`; they are kept here so that the
yardstick does not move when the program's smoke script does.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import pathlib
import sys
import time
import zlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(Exception):
    """The run cannot measure this cell: no result line is printed."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path, name: str):
    """Import the file at `path` (drivers and metric readers are found by
    name, and their file names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_program(root=ROOT) -> None:
    """Put the program under test (`<root>/src`) on the import path."""
    src = str(pathlib.Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def device_info(chips: int, peaks: dict) -> dict:
    """The devices JAX reports. Raises BenchError unless they are TPUs,
    at least `chips` of them, of a kind the peaks table knows."""
    import jax

    backend = jax.default_backend()
    devs = jax.devices()
    if backend != "tpu" or devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's backend is {backend!r}")
    if len(devs) < chips:
        raise BenchError(f"{chips} chips wanted, {len(devs)} found")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """The peak of `peak_bytes_in_use` over the chips used (0 where the
    backend reports none)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def init_compile_cache(root=ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however short its compile."""
    import jax

    path = str(pathlib.Path(root) / ".bench_cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts XLA programs built while active. JAX records the backend
    compile event for a program loaded from the persistent cache too, so
    compiled = events - cache hits."""

    def __init__(self):
        import jax

        self.events = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.events += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.events - self.hits

    def snapshot(self) -> tuple:
        return self.compiled, self.hits

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)


def settle_heap() -> str:
    """End of set-up: one full collection, then every object alive is
    frozen, so that the collector's later passes in the window walk only
    what the window makes. Returns a line for the log."""
    n = len(gc.get_objects())
    t = time.perf_counter()
    gc.collect()
    took = time.perf_counter() - t
    gc.freeze()
    return (f"end of set-up: a full collection took {took:.6f} s over {n} "
            f"tracked objects; the {gc.get_freeze_count()} left are frozen")


class GcPauses:
    """The garbage collector's passes while active: (generation, seconds,
    `time.perf_counter()` at the pass's end)."""

    def __init__(self):
        self.passes, self._t = [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None:
            self.passes.append((info["generation"], now - self._t, now))
            self._t = None

    def summary(self, t0: float, t1: float) -> str:
        inside = [p for p in self.passes if t0 <= p[2] < t1]
        by_gen = [sum(1 for p in inside if p[0] == g) for g in range(3)]
        longest = max(inside, key=lambda p: p[1], default=(None, 0.0, t0))
        return (f"collector passes in the window by generation {by_gen}; "
                f"longest {longest[1] * 1e3:.3f} ms (generation "
                f"{longest[0]}, {longest[2] - t0:.3f} s in)")

    def close(self) -> None:
        gc.callbacks.remove(self._on)


# ---------------------------------------------------------------------------
# seeds, keys, samples
# ---------------------------------------------------------------------------


def jax_key(seed: int, salt: str = ""):
    """A JAX key from any seed below 2**64 (more than 32 bits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seed = (int(seed) ^ zlib.crc32(salt.encode())) % 2**64
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile of raw samples (q in (0, 100]); a missing
    sample (inf) sorts last."""
    xs = sorted(samples)
    if not xs:
        return math.inf
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


# ---------------------------------------------------------------------------
# configuration file -> its architecture's module
# ---------------------------------------------------------------------------


def arch(conf: dict, root=ROOT):
    """The module of the configuration's architecture,
    `bench/arch/<model_type>.py`: all that the drivers and readers know of
    a model (the program's `ModelCfg`, the weight layout, the counts of
    the work a step needs, the plain reference). The only place an
    architecture is chosen."""
    name = conf["model_type"]
    path = pathlib.Path(root) / "bench" / "arch" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no architecture module bench/arch/{name}.py for "
                         f"model_type {name!r}")
    return load_module(path, f"bench_arch_{name}")
