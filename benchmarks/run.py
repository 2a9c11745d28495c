"""Benchmark entrypoint: one harness per paper table/figure + kernels +
roofline. Prints ``name,us_per_call,derived`` CSV rows; ``--json`` also
writes the rows as a machine-readable file (the CI bench lane uploads it
as an artifact, giving the repo a bench trajectory across commits).
Payloads are self-describing (git SHA, UTC timestamp, schema version) so
``--history``/``--check-regression`` can maintain and gate on a
``BENCH_history.jsonl`` trajectory via `repro.obs.regress`.

  PYTHONPATH=src python -m benchmarks.run            # fast (minutes, CPU)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale budgets
  PYTHONPATH=src python -m benchmarks.run --only table3,roofline
  PYTHONPATH=src python -m benchmarks.run --only table3,kernels \
      --json results/BENCH_ci.json \
      --history results/BENCH_history.jsonl --check-regression
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time
import traceback


def _git_sha() -> str:
    """Commit this run measures: local git first, CI env as fallback."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
            check=True).stdout.strip()
    except Exception:
        return os.environ.get("GITHUB_SHA", "unknown")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale budgets (slow)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: table2,table3,table4,"
                         "table5,fig5,kernels,roofline,swap,quant,sparse,"
                         "paged,spec,optim,obs")
    ap.add_argument("--json", default="",
                    help="write rows as JSON: {suites: {name: [{name, "
                         "us_per_call, derived}]}} plus run metadata")
    ap.add_argument("--history", default="",
                    help="BENCH_history.jsonl trajectory: the run is "
                         "appended after the (optional) regression check")
    ap.add_argument("--check-regression", action="store_true",
                    help="gate this run against the history's "
                         "median-of-history baseline (requires --history); "
                         "exits non-zero on regression")
    ap.add_argument("--regression-tolerance", type=float, default=None,
                    help="allowed slowdown vs baseline before failing "
                         "(fraction; default repro.obs.regress's 0.5)")
    args = ap.parse_args()
    fast = not args.full
    only = set(filter(None, args.only.split(",")))
    if args.check_regression and not args.history:
        ap.error("--check-regression requires --history")

    import jax

    from repro.common.runtime import init_compile_cache

    init_compile_cache()
    from benchmarks import (common, fig5_patterns, kernel_bench, obs_bench,
                            optim_bench, paged_bench, quant_bench, roofline,
                            sparse_bench, spec_bench, swap_churn,
                            table2_two_stage, table3_param_counts,
                            table4_module_ablation, table5_layer_sweep)

    suites = [
        ("table3", table3_param_counts.run),   # fast + exact: run first
        ("kernels", kernel_bench.run),
        ("swap", swap_churn.run),
        ("quant", quant_bench.run),
        ("sparse", sparse_bench.run),
        ("paged", paged_bench.run),
        ("spec", spec_bench.run),
        ("optim", optim_bench.run),
        ("obs", obs_bench.run),
        ("roofline", roofline.run),
        ("table2", table2_two_stage.run),
        ("table4", table4_module_ablation.run),
        ("table5", table5_layer_sweep.run),
        ("fig5", fig5_patterns.run),
    ]

    unknown = only - {name for name, _ in suites}
    if unknown:
        ap.error(f"unknown --only suites: {sorted(unknown)} "
                 f"(known: {sorted(name for name, _ in suites)})")

    failures = []
    per_suite = {}
    t0 = time.time()
    for name, fn in suites:
        if only and name not in only:
            continue
        print(f"\n=== {name} ===", flush=True)
        start = len(common.ROWS)
        try:
            fn(fast=fast)
            # a suite that "succeeds" while recording nothing is a silent
            # skip (broken harness, short-circuited budget): fail loudly -
            # the CI bench lane's trajectory point would otherwise just
            # quietly lose its rows. Suites with a legitimate reason to
            # sit a run out must declare it via common.skip().
            if len(common.ROWS) == start and name not in common.SKIPPED:
                raise RuntimeError(f"suite {name!r} recorded no rows")
        except Exception:
            failures.append(name)
            traceback.print_exc()
        per_suite[name] = [
            {"name": r["name"], "us_per_call": r["us"], "derived": r["derived"]}
            for r in common.ROWS[start:]
        ]
    elapsed = time.time() - t0
    print(f"\n# benchmarks done in {elapsed:.0f}s; "
          f"failures: {failures or 'none'}")

    now = time.time()
    payload = {
        "schema": "repro-bench-v2",
        "git_sha": _git_sha(),
        "created_unix": now,
        "created_utc": datetime.datetime.fromtimestamp(
            now, datetime.timezone.utc).isoformat(),
        "backend": jax.default_backend(),
        "fast": fast,
        "elapsed_s": elapsed,
        "failures": failures,
        "skipped": common.SKIPPED,
        "suites": per_suite,
    }
    if args.json:
        out_dir = os.path.dirname(args.json)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {sum(map(len, per_suite.values()))} rows "
              f"to {args.json}")

    regressed = False
    if args.history:
        from repro.obs import regress

        history = regress.load_history(args.history)
        if args.check_regression:
            kwargs = {}
            if args.regression_tolerance is not None:
                kwargs["tolerance"] = args.regression_tolerance
            report = regress.check_regression(history, payload, **kwargs)
            for line in report.summary_lines():
                print(line)
            regressed = not report.ok
        # the trajectory records bad runs too - a regression that later
        # "recovers" to the same speed should not shift the baseline
        regress.append_history(args.history, regress.history_entry(payload))
        print(f"# appended run to {args.history} ({len(history) + 1} "
              "entries)")

    if failures or regressed:
        sys.exit(1)


if __name__ == "__main__":
    main()
