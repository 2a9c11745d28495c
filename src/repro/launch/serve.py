"""Serving launcher: continuous-batching generation on a (reduced) config,
optionally with per-request multi-task Hadamard adapters.

Requests arrive with staggered prompt lengths, budgets and task ids; the
scheduler admits them into `--num-slots` KV-cache slots mid-decode and
retires them as they finish, printing a throughput/latency report
(requests/s, tokens/s, mean time-to-first-token).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --requests 8 --num-slots 4 --prompt-len 16 --new-tokens 8 --tasks 3

Multi-tenant hot-swap: with `--adapter-dir` the per-task deltas live in an
on-disk AdapterRegistry and requests address adapters by NAME; only
`--bank-size` rows are device-resident at once (LRU eviction, pinned while
in flight), and a task published mid-stream is admitted without rebuilding
the engine or retracing the decode tick:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --requests 12 --tasks 6 --bank-size 2 --adapter-dir /tmp/adapters

Speculative decoding (`--spec-k`): draft k tokens per tick with the
adapter-free backbone and verify them in one forward - greedy output is
token-identical, ticks shrink by the acceptance rate:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --requests 8 --num-slots 4 --spec-k 4 --tasks 3

SLOs and admission control (`--slo-*`, `--admission`): declare latency /
queue / KV / acceptance objectives, evaluated as multi-window burn rates
over the live metrics; with `--admission` the degradation ladder sheds
and defers admissions (and steps speculation down) to protect in-flight
requests - activity shows up in the scheduler report's shed/deferred/
degrade rows and as `shed`/`degrade` events in `--events-file`.

All serving knobs funnel into one validated `ServingConfig`; the
scheduler (contiguous / paged / speculative) is selected by
`serving.make_scheduler`. `--static` falls back to the lock-step
ServeEngine.generate batch (the pre-scheduler path, kept for A/B
comparison).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.common.runtime import init_compile_cache
from repro.configs import get, get_smoke
from repro.core import peft
from repro.core.hadamard import extract_delta, perturb_adapters
from repro.dist.api import use_mesh
from repro.launch.mesh import parse_mesh
from repro.models import model as M
from repro.obs import (JsonlSink, MetricsRegistry, ProfiledTicks, SLOSpec,
                       accept_floor, kv_free_floor, queue_depth_max,
                       tpot_target, ttft_target, write_snapshot)
from repro.serving import (AdapterBank, AdapterRegistry, AdmissionConfig,
                           AdmissionShedError, MultiTaskEngine, Request,
                           Scheduler, ServeEngine, ServingConfig,
                           format_report, make_scheduler)


def build_params(key, cfg, tasks: int, share_w: bool = False):
    """Backbone params, plus per-task adapter variants when tasks > 0
    (distinct adapters per task, as if fine-tuned per task). share_w
    builds the paper's Fig-5 world: ONE w perturbation common to every
    task, per-task b - the regime the shared-w bank factorizes exactly."""
    base = M.init_params(key, cfg)
    if tasks <= 0:
        return base, None
    if share_w:
        stem = perturb_adapters(base, jax.random.fold_in(key, 7),
                                leaves=("w",))
        return base, [
            perturb_adapters(stem, jax.random.fold_in(key, 100 + t),
                             leaves=("b",))
            for t in range(tasks)
        ]
    return base, [
        perturb_adapters(base, jax.random.fold_in(key, 100 + t))
        for t in range(tasks)
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)

    g = ap.add_argument_group("workload")
    g.add_argument("--requests", type=int, default=8,
                   help="number of requests to serve")
    g.add_argument("--prompt-len", type=int, default=16,
                   help="max prompt length (requests are staggered below it)")
    g.add_argument("--new-tokens", type=int, default=8,
                   help="max generation budget per request")
    g.add_argument("--static", action="store_true",
                   help="lock-step ServeEngine.generate batch instead of "
                        "the continuous-batching scheduler")

    g = ap.add_argument_group("capacity (ServingConfig)")
    g.add_argument("--num-slots", type=int, default=4,
                   help="KV-cache slots (max concurrent requests)")

    g = ap.add_argument_group("paged KV (ServingConfig)")
    g.add_argument("--page-size", type=int, default=0,
                   help=">0: paged KV serving (serving/paged.py) - block-"
                        "table cache with this many tokens per page, "
                        "copy-on-write prefix sharing and admission gated "
                        "on free blocks instead of whole slots")
    g.add_argument("--kv-blocks", type=int, default=0,
                   help="physical blocks in the paged pool (0 = size for "
                        "num_slots worst-case requests plus 50%% headroom)")
    g.add_argument("--prefix-cache", dest="prefix_cache",
                   action="store_true", default=True,
                   help="share identical prompt prefixes across requests "
                        "(default on; paged mode only)")
    g.add_argument("--no-prefix-cache", dest="prefix_cache",
                   action="store_false")
    g.add_argument("--kv-quant", default="", choices=["", "int8", "fp8"],
                   help="store paged KV blocks quantized with per-token "
                        "scales (4x smaller than fp32; dequantized at the "
                        "attention gather)")

    g = ap.add_argument_group("speculation (ServingConfig)")
    g.add_argument("--spec-k", type=int, default=0,
                   help=">0: speculative decoding - draft this many tokens "
                        "per tick and verify them in one target forward "
                        "(greedy output stays token-identical)")
    g.add_argument("--spec-draft", default="self", choices=["self", "model"],
                   help="draft source: 'self' drafts with the adapter-free "
                        "frozen backbone (identity Hadamard rows, no extra "
                        "weights); 'model' drafts with a separate model "
                        "(here: the untuned base checkpoint)")

    g = ap.add_argument_group("adapters / tenants")
    g.add_argument("--tasks", type=int, default=0,
                   help=">0: multi-task adapter bank serving")
    g.add_argument("--adapter-dir", default="",
                   help="hot-swap serving: publish/load per-task deltas "
                        "through an AdapterRegistry at this path; requests "
                        "carry adapter NAMES resolved at admission")
    g.add_argument("--bank-size", type=int, default=4,
                   help="device-resident adapter rows for --adapter-dir "
                        "(misses load from disk, cold rows are evicted LRU)")
    g.add_argument("--prune-to", type=int, default=0,
                   help="repro.sparse: prune every tenant's adapter to its "
                        "top-K layers and publish PACKED deltas (bitmask + "
                        "active rows; pruned layers serve as identity). "
                        "0 = dense; the paper's 0.022%% preset is K = 2L/3")
    g.add_argument("--share-w", action="store_true",
                   help="repro.sparse shared-w serving (paper Fig 5: w is "
                        "task-invariant): the bank stores ONE shared w "
                        "row-set and per-tenant inserts scatter only b - "
                        "T tenants cost (T+1) row-sets instead of 2T. "
                        "Requires --adapter-dir")

    g = ap.add_argument_group("observability (repro.obs)")
    g.add_argument("--metrics-every", type=int, default=0,
                   help=">0: print a one-line metrics digest every N "
                        "scheduler ticks")
    g.add_argument("--metrics-file", default="",
                   help="write the final MetricsRegistry snapshot here "
                        "(JSON; a .prom suffix writes Prometheus text "
                        "exposition instead)")
    g.add_argument("--events-file", default="",
                   help="append structured events (retraces, bank "
                        "evictions/pin stalls, stragglers) as JSONL here")
    g.add_argument("--profile-dir", default="",
                   help="capture a JAX profiler trace of the first "
                        "--profile-ticks scheduler ticks into this "
                        "directory (TensorBoard/Perfetto-loadable)")
    g.add_argument("--profile-ticks", type=int, default=8,
                   help="scheduler ticks the --profile-dir capture spans")

    g = ap.add_argument_group("SLOs / admission control")
    g.add_argument("--slo-ttft-ms", type=float, default=0,
                   help=">0: TTFT objective - --slo-target of requests "
                        "must see first token under this many ms")
    g.add_argument("--slo-tpot-ms", type=float, default=0,
                   help=">0: per-output-token latency objective")
    g.add_argument("--slo-queue-depth", type=int, default=0,
                   help=">0: queued requests must stay at or under this")
    g.add_argument("--slo-kv-free", type=int, default=0,
                   help=">0: paged KV pool must keep this many free blocks")
    g.add_argument("--slo-accept", type=float, default=0,
                   help=">0: speculative acceptance-rate floor (0..1)")
    g.add_argument("--slo-target", type=float, default=0.95,
                   help="good fraction the latency/gauge objectives must "
                        "hold (error budget = 1 - target)")
    g.add_argument("--admission", action="store_true",
                   help="act on SLO breaches with the degradation ladder: "
                        "stop prefix fill -> step spec_k down -> defer -> "
                        "shed (serving/admission.py); without this, "
                        "breaches only land as registry events")
    g.add_argument("--admission-check-every", type=int, default=4,
                   help="evaluate the SLO monitor every N scheduler ticks")

    g = ap.add_argument_group("engine / sampling")
    g.add_argument("--top-k", type=int, default=0,
                   help=">0: per-request top-k sampling (greedy otherwise)")
    g.add_argument("--stream", action="store_true",
                   help="print every token the moment it is sampled")
    g.add_argument("--fold", action="store_true",
                   help="fold the adapter into W_O (zero-overhead serving)")
    g.add_argument("--quant", default="", choices=["", "int8", "fp8"],
                   help="quantize the frozen backbone's matmul weights at "
                        "placement (adapter rows and norms stay fp32)")
    g.add_argument("--mesh", default="",
                   help="'DATAxMODEL' (e.g. 2x4): serve the backbone "
                        "sharded over a host mesh")
    args = ap.parse_args()
    init_compile_cache()

    mesh = parse_mesh(args.mesh)
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    cfg = peft.attach(cfg, peft.strategy("hadamard"))
    key = jax.random.PRNGKey(args.seed)
    if args.share_w and not args.adapter_dir:
        raise SystemExit("--share-w factorizes the hot-swap bank "
                         "(pass --adapter-dir)")
    base, variants = build_params(key, cfg, args.tasks, share_w=args.share_w)

    layer_mask = None
    if args.prune_to:
        from repro.sparse import apply_layer_mask, depth_mask, n_layers

        try:
            layer_mask = depth_mask(cfg, args.prune_to)
        except ValueError as e:
            raise SystemExit(f"--prune-to: {e}")
        if variants is not None:
            # prune at the source: pruned layers are identity everywhere,
            # so packed publishing below is an exact round trip
            variants = [apply_layer_mask(v, cfg, layer_mask)
                        for v in variants]
        print(f"pruned serving: top {args.prune_to}/{n_layers(cfg)} "
              "layers active, packed deltas published")

    def task_delta(params):
        """Registry payload for one tenant: packed when pruning."""
        from repro.sparse import prune_delta

        delta = extract_delta(params)
        if layer_mask is not None:
            delta = prune_delta(delta, cfg, layer_mask)
        return delta

    registry = None
    if args.adapter_dir:
        if variants is None:
            raise SystemExit("--adapter-dir requires --tasks > 0")
        if args.static:
            raise SystemExit("--adapter-dir serves through the scheduler "
                             "(drop --static)")
        # trainer side of the lifecycle: publish every task's KB-sized
        # delta as a named, versioned registry entry (all but the last -
        # that one is published mid-stream below to demonstrate runtime
        # tenant onboarding)
        registry = AdapterRegistry(args.adapter_dir)
        for t, params in enumerate(variants[:-1] or variants):
            registry.publish(f"task{t}", task_delta(params))

    quant = args.quant or None
    with use_mesh(mesh):  # engine captures the mesh; params placed sharded
        if registry is not None:
            bank_base = base
            if args.share_w:
                from repro.sparse import factorize, shared_w_overlay

                sa = factorize(
                    {f"task{t}": extract_delta(v)
                     for t, v in enumerate(variants)}, cfg, mask=layer_mask)
                bank_base = shared_w_overlay(base, sa)
            bank = AdapterBank(cfg, bank_base, args.bank_size, registry,
                               shared_w=args.share_w)
            engine = MultiTaskEngine(cfg, bank, quant=quant)
        elif variants is not None:
            engine = MultiTaskEngine(cfg, variants, quant=quant)
        else:
            engine = ServeEngine(cfg, base, fold=args.fold, quant=quant)
    if quant:
        from repro.quant import quant_summary

        qs = quant_summary(engine.bank if isinstance(engine, MultiTaskEngine)
                           else engine.params)
        print(f"{quant} backbone: {qs['n_quantized_leaves']} matmul leaves, "
              f"{qs['dense_bytes_fp32'] / 2**20:.2f} MiB fp32 -> "
              f"{qs['quantized_bytes'] / 2**20:.2f} MiB "
              f"({qs['ratio']:.2f}x); tree total "
              f"{qs['total_bytes'] / 2**20:.2f} MiB")

    rs = np.random.RandomState(args.seed)
    n = args.requests
    if args.static:
        tokens = np.asarray(jax.random.randint(
            key, (n, args.prompt_len), 10, cfg.vocab_size))
        t0 = time.perf_counter()
        if variants is not None:
            reqs = [Request(prompt=tokens[i], max_new_tokens=args.new_tokens,
                            task_id=int(i % args.tasks)) for i in range(n)]
            out = np.stack(engine.generate(
                reqs,
                rng=jax.random.PRNGKey(args.seed) if args.top_k else None,
                top_k=args.top_k))
        else:
            out = engine.generate(
                tokens, args.new_tokens,
                rng=jax.random.PRNGKey(args.seed) if args.top_k else None,
                top_k=args.top_k)
        dt = time.perf_counter() - t0
        print(f"static batch: generated {out.shape} in {dt:.2f}s "
              f"({n * args.new_tokens / dt:.1f} tok/s)")
        print(out[:, :8])
        return

    # heterogeneous request stream: staggered prompt lengths and budgets
    requests = []
    for i in range(n):
        plen = int(rs.randint(max(1, args.prompt_len // 2),
                              args.prompt_len + 1))
        budget = int(rs.randint(max(1, args.new_tokens // 2),
                                args.new_tokens + 1))
        kw = {}
        if registry is not None:
            kw["adapter"] = f"task{i % args.tasks}"
        elif args.tasks > 0:
            kw["task_id"] = i % args.tasks
        requests.append(Request(
            prompt=rs.randint(10, cfg.vocab_size, size=(plen,)),
            max_new_tokens=budget,
            top_k=args.top_k,
            seed=args.seed + i,
            **kw,
        ))

    stream = None
    if args.stream:
        def stream(rid, tok):
            print(f"  req{rid} += {tok}", flush=True)

    # bucket prompt lengths where the config allows it so the staggered
    # request stream doesn't compile one prefill per distinct length
    max_len = args.prompt_len + args.new_tokens + args.spec_k
    bucket = 8 if Scheduler.supports_bucketing(cfg) else None
    paged = args.page_size > 0
    if paged:
        max_len = -(-max_len // args.page_size) * args.page_size
        if bucket is not None and bucket % args.page_size:
            bucket = args.page_size * (-(-bucket // args.page_size))
    draft_model = None
    if args.spec_k and args.spec_draft == "model":
        draft_model = (cfg, base)  # the untuned base checkpoint drafts
    # one registry for the whole serve: every scheduler/bank/cache series,
    # the per-request tracer, and any attached exporters report into it
    obs = MetricsRegistry()
    events_sink = None
    if args.events_file:
        events_sink = JsonlSink(args.events_file)
        obs.add_sink(events_sink)
    objectives = []
    if args.slo_ttft_ms > 0:
        objectives.append(ttft_target(args.slo_ttft_ms,
                                      target=args.slo_target))
    if args.slo_tpot_ms > 0:
        objectives.append(tpot_target(args.slo_tpot_ms,
                                      target=args.slo_target))
    if args.slo_queue_depth > 0:
        objectives.append(queue_depth_max(args.slo_queue_depth,
                                          target=args.slo_target))
    if args.slo_kv_free > 0:
        if not paged:
            raise SystemExit("--slo-kv-free needs paged KV (--page-size)")
        objectives.append(kv_free_floor(args.slo_kv_free,
                                        target=args.slo_target))
    if args.slo_accept > 0:
        if not args.spec_k:
            raise SystemExit("--slo-accept needs speculation (--spec-k)")
        objectives.append(accept_floor(args.slo_accept))
    if args.admission and not objectives:
        raise SystemExit("--admission needs at least one --slo-* objective")
    slo = SLOSpec(objectives=tuple(objectives)) if objectives else None
    admission = (AdmissionConfig(check_every=args.admission_check_every)
                 if args.admission else None)
    if slo is not None:
        print("SLOs: " + ", ".join(o.name for o in objectives)
              + (" (admission ladder armed)" if args.admission
                 else " (monitor only)"))
    try:
        serve_cfg = ServingConfig(
            num_slots=args.num_slots, max_len=max_len, paged=paged,
            page_size=args.page_size if paged else 16,
            num_blocks=(args.kv_blocks or None) if paged else None,
            prefix_cache=args.prefix_cache, kv_quant=args.kv_quant or None,
            spec_k=args.spec_k, spec_draft=args.spec_draft,
            backbone_quant=quant, prefill_bucket=bucket, top_k=args.top_k,
            stream=stream, slo=slo, admission=admission)
        sched = make_scheduler(engine, serve_cfg, draft_model=draft_model,
                               obs=obs)
    except ValueError as e:
        raise SystemExit(str(e))

    prof = (ProfiledTicks(args.profile_dir, n=args.profile_ticks)
            if args.profile_dir else None)

    def step_once():
        """One scheduler tick plus the launcher-side obs hooks."""
        sched.step()
        if prof is not None:
            prof.tick()
        if (args.metrics_every and sched._ticks
                and sched._ticks % args.metrics_every == 0):
            snap = obs.snapshot()
            tok = sum(v for k, v in snap["counters"].items()
                      if k.startswith("serve_tokens_total"))
            print(f"[obs] tick {sched._ticks}: {tok} tokens emitted, "
                  f"{sched.active} active, {sched.pending} queued, "
                  f"{snap['events_by_kind'].get('retrace', 0)} retrace "
                  "events", flush=True)
    if paged:
        print(f"paged KV: {sched.alloc.num_blocks - 1} x "
              f"{args.page_size}-token blocks"
              + (f", {args.kv_quant} blocks" if args.kv_quant else "")
              + ("" if args.prefix_cache else ", prefix cache off"))
    if args.spec_k:
        print(f"speculative decoding: k={args.spec_k}, "
              f"draft={args.spec_draft}")

    if registry is not None and args.tasks > 1:
        # multi-tenant lifecycle: the LAST task's tenant shows up only
        # after serving has started - publish + serve it mid-stream with
        # no engine rebuild (and, asserted below, no decode retrace)
        hot = f"task{args.tasks - 1}"
        early = [r for r in requests if r.adapter != hot]
        late = [r for r in requests if r.adapter == hot]
        t0 = time.perf_counter()
        ids = [sched.submit(r) for r in early]
        while sched.pending or sched.active or late:
            step_once()
            if late and len(sched.completions) * 2 >= len(early):
                registry.publish(hot, task_delta(variants[-1]))
                print(f"  ++ runtime add: published {hot!r}, submitting "
                      f"{len(late)} request(s) for it mid-stream")
                for r in late:
                    try:
                        ids.append(sched.submit(r))
                    except AdmissionShedError as e:
                        print(f"  !! shed: {e}")
                late = []
        elapsed = time.perf_counter() - t0
        done = [sched.completions.pop(i) for i in ids]
        # the scheduler's own report (quantiles included) - the launcher
        # no longer recomputes throughput/latency on the side
        report = sched.report(done, elapsed, ticks=sched._ticks)
        # runtime remove: retire the first tenant - future loads fail,
        # its device row is freed for the next miss
        victim = "task0"
        registry.remove(victim)
        engine.adapter_bank.invalidate(victim)
        bank = engine.adapter_bank.stats()
        print(f"  -- runtime remove: {victim!r} unpublished + row freed")
        print(f"adapter bank: {bank['resident']}/{bank['size']} rows "
              f"resident, {bank['loads']} loads, {bank['evictions']} "
              f"evictions; decode traced {engine.trace_counts['decode']}x")
        print(f"bank adapter bytes: {bank['adapter_bytes'] / 1024:.1f} KiB"
              + (" (shared-w: one w row-set for all tenants)"
                 if bank["shared_w"] else ""))
    else:
        t0 = time.perf_counter()
        ids = [sched.submit(r) for r in requests]
        while sched.pending or sched.active:
            step_once()
        elapsed = time.perf_counter() - t0
        done = [sched.completions.pop(i) for i in ids]
        report = sched.report(done, elapsed, ticks=sched._ticks)

    for c in done:
        who = c.adapter if c.adapter is not None else f"task{c.task_id}"
        print(f"req{c.request_id} {who} prompt={c.prompt_len} "
              f"-> {len(c.tokens)} tok ({c.finish_reason}, "
              f"ttft {c.ttft_s * 1e3:.0f}ms): {c.tokens[:8].tolist()}")
    print(f"served {report['requests']} requests / {report['tokens']} tokens "
          f"in {report['elapsed_s']:.2f}s over {report['ticks']} ticks "
          f"({args.num_slots} slots)")
    print("scheduler report:")
    print(format_report(report))
    if args.spec_k:
        st = sched.spec_stats
        print(f"speculation: {st['accepted']}/{st['drafted']} drafts "
              f"accepted ({sched.acceptance_rate:.0%}) over "
              f"{st['spec_ticks']} verify ticks")
    if args.page_size > 0:
        pr = sched.pool_report()
        print(f"pool: {pr['live_blocks']}/{pr['num_blocks']} blocks live, "
              f"{pr['prefix_full_entries']} cached prompts; "
              f"{pr['full_hits']} full / {pr['partial_hits']} partial "
              f"prefix hits, {pr['cold']} cold prefills")

    if slo is not None:
        breaches = obs.events_of("slo_breach")
        print(f"SLO: {len(breaches)} breach event(s)"
              + (f" ({', '.join(sorted({e['objective'] for e in breaches}))})"
                 if breaches else "")
              + (f"; ladder level {report['degrade_level']}, "
                 f"{report['shed']} shed, {report['deferred_ticks']} "
                 "deferred tick(s)" if args.admission else ""))
    n_retrace = len(obs.events_of("retrace"))
    if n_retrace:
        print(f"WARNING: {n_retrace} mid-serve retrace event(s) - see "
              "--events-file for details")
    if prof is not None:
        prof.stop()
        print(f"profiler trace -> {args.profile_dir}")
    if args.metrics_file:
        write_snapshot(obs, args.metrics_file)
        print(f"metrics snapshot -> {args.metrics_file}")
    if events_sink is not None:
        events_sink.close()


if __name__ == "__main__":
    main()
