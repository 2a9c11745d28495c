"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` `workloads`) names a configuration and a
traffic mix. The harness reads the configuration's file, the module of
the architecture its `model_type` names (`bench/arch/<model_type>.py`),
the traffic file `bench/traffic/<traffic>.json`, and the driver that
file names (`bench/drivers/<driver>.py`); with `--trace 1` it reduces a
profiler trace of a steady stretch and reads each per-layer metric that
applies to the cell with its own reader, `bench/metrics/<metric>.py`.
So a new cell, configuration, traffic mix or per-layer metric is new
files and `BENCHMARK.json` entries, with no edit here, and a new
architecture is a module under `bench/arch/` and a configuration file.

The last stdout line is one JSON object: correct, attempted, failed,
metrics (the end-to-end metrics, or with `--trace 1` the per-layer
ones), device, with `--trace 1` a breakdown, and last the numbers
compared for `correct` beside their limits, which also end stderr.
Without a TPU of a kind `bench/peaks.json` knows, or with fewer chips
than the cell asks for, it exits 2 and prints no result.

`--control` runs the cell's control, one precision below the stated
one (the driver says which), which `correct` has to reject, and
`--fault <name>` plants a fault of `bench/faults.py`; neither is part of
a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # import the harness as the package `bench`, never its files as
    # top-level modules (bench/trace.py would hide the standard `trace`)
    sys.path[:] = [p for p in sys.path
                   if pathlib.Path(p or ".").resolve() != ROOT / "bench"]
    sys.path.insert(0, str(ROOT))

from bench import common, faults, trace as tracing  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root, workload: str):
    """(benchmark, workload entry, configuration dict, traffic dict)."""
    root = pathlib.Path(root)
    bench = common.load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise common.BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    conf = common.load_json(root / conf_entry["file"])
    traffic = common.load_json(root / "bench" / "traffic"
                               / f"{w['traffic']}.json")
    return bench, w, conf, traffic


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, fault=None, require_chip: bool = True,
             t_start: float = T_START) -> dict:
    """Run one cell and return the result line's object. require_chip=False
    skips the look for a TPU (the CPU tests drive the rest of a run)."""
    import jax

    root = pathlib.Path(root)
    bench, w, conf, traffic = load_cell(root, workload)
    arch = common.arch(conf, root)
    peaks = common.load_json(root / "bench" / "peaks.json")
    if require_chip:
        device = common.device_info(w["chips"], peaks)
        common.init_compile_cache(root)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": w["chips"]}
    common.use_program(root)
    compiles, gc_passes = common.CompileCounter(), common.GcPauses()
    undo = faults.FAULTS[fault]() if fault else None
    try:
        driver = common.load_module(
            root / "bench" / "drivers" / f"{traffic['driver']}.py",
            f"bench_driver_{traffic['driver']}")
        ctx = SimpleNamespace(
            root=root, config=conf, arch=arch, traffic=traffic, seed=seed,
            seconds=seconds, trace=trace, control=control, chips=w["chips"],
            compiles=compiles, gc_passes=gc_passes, t_start=t_start, log=log)
        res = driver.run(ctx)
    finally:
        compiles.close()
        gc_passes.close()
        gc.unfreeze()  # a driver freezes its set-up's objects
        if undo is not None:
            undo()

    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in bench["end_to_end"]:
            if applies(m, workload):
                out["metrics"][m["name"]] = {"value": res["e2e"][m["name"]],
                                             "unit": m["unit"]}
    else:
        reduced = tracing.reduce(res["trace"]) if res["trace"] else {}
        record = dict(res["record"], trace=reduced, workload=workload,
                      peak=peaks.get(device["kind"], {}))
        for m in bench["per_layer"]:
            if not applies(m, workload):
                continue
            reader = common.load_module(
                root / "bench" / "metrics" / f"{m['name']}.py",
                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(record)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced.get("devices"):
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in res["checks"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the cell's lower-precision control")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS),
                    help="plant a fault under the timed path")
    args = ap.parse_args(argv)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), control=args.control,
                       fault=args.fault)
    except common.BenchError as e:
        log(f"FAILED: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
