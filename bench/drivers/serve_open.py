"""Open-loop serving of many tenants' Hadamard adapters on one frozen
backbone, through `make_scheduler` and `MultiTaskEngine`.

Set-up: weights from the seed, the scheduler the traffic file's
`serving` block describes, one warm-up request per prompt bucket (every
prefill, insert and decode shape the traffic can produce), then a
lead-in of arrivals that brings the slots to steady state. The window
is the next `--seconds`; arrivals go on after it until every request
due in it has finished (at most `drain_max_s` more). Latencies run from
the time a request was due, not from when it was submitted.

Correct: after the window, a seeded sample of the finished requests,
the longest among them, is run through the architecture's plain float32
reference (its module's `served_gaps`) over prompt + served tokens; at
each served token the reference's best logit less that token's logit is
the gap, and the widest gap over the sample is held to the traffic
file's limit.
Every request due in the window must also have answered: one still
streaming when the drain ends is late (missing from the latency
percentiles), one that produced no token never came. The control
(`--control`) is the reference with float8 matmul weights in the
program's place, read at the same positions of the same sample.
"""
from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from bench import common, traffic as gen, weights
from bench.trace import Stretch


class Req:
    __slots__ = ("plan", "due", "submit", "first", "last", "tokens", "times")

    def __init__(self, plan, due):
        self.plan, self.due = plan, due
        self.submit = self.first = self.last = None
        self.tokens, self.times = [], []


def build_engine(conf, cfg, layout, key, tenants: int):
    """A MultiTaskEngine over the seed's weights: one task tree per bank
    row, sharing every backbone array."""
    from repro.serving import MultiTaskEngine

    weights.check_layout(layout, weights.program_shapes(cfg, tenants))
    flat = weights.flatten(weights.make(key, layout, conf["initializer_range"]))
    tasks = [weights.nest({p: (v[:, t] if "/adapter/" in p else v)
                           for p, v in flat.items()})
             for t in range(tenants)]
    return MultiTaskEngine(cfg, tasks)


def run(ctx) -> dict:
    import jax
    from repro.serving import Request, ServingConfig, make_scheduler

    conf, tr = ctx.config, ctx.traffic
    T = tr["tenants"]["n"]
    layout = ctx.arch.layout(conf, T)
    engine = build_engine(conf, ctx.arch.program_cfg(conf), layout,
                          common.jax_key(ctx.seed, "weights"), T)
    model = ctx.arch.Counts(conf)

    reqs: dict = {}          # scheduler request id -> Req
    live: set = set()        # ids between first and last token
    in_stretch = {"decode": [], "prefill": []}
    clock = time.perf_counter

    def on_token(rid, tok):
        r = reqs.get(rid)
        if r is None:        # a warm-up request
            return
        now = clock()
        if r.first is None:
            r.first = now
            live.add(rid)
        r.tokens.append(tok)
        r.times.append(now)
        r.last = now
        if len(r.tokens) == r.plan.max_new_tokens:
            live.discard(rid)

    scfg = ServingConfig(**tr["serving"], stream=on_token)
    sched = make_scheduler(engine, scfg)
    stretch = None

    # bench spans around the calls into the engine, and the work each
    # traced call needs (positions come from the tokens streamed so far)
    prefill, decode = engine.prefill, engine.paged_decode_step

    def traced_prefill(tokens, cache_len, task_ids=None, last_pos=None):
        with jax.profiler.TraceAnnotation("prefill"):
            out = prefill(tokens, cache_len, task_ids=task_ids,
                          last_pos=last_pos)
        if stretch is not None and stretch.active:
            in_stretch["prefill"].append(
                int(last_pos) + 1 if last_pos is not None
                else int(np.shape(tokens)[1]))
        return out

    def traced_decode(pool, tok, pos, tables, task_ids=None):
        if stretch is not None and stretch.active:
            in_stretch["decode"].append(
                [len(reqs[r].plan.prompt) + len(reqs[r].tokens) - 1
                 for r in live])
        with jax.profiler.TraceAnnotation("decode"):
            return decode(pool, tok, pos, tables, task_ids=task_ids)

    engine.prefill, engine.paged_decode_step = traced_prefill, traced_decode

    # warm-up: one request per prompt bucket, so every prefill and insert
    # shape (and the decode tick) is built before the window
    bucket, max_len = tr["serving"]["prefill_bucket"], tr["serving"]["max_len"]
    top = tr["prompt_len"]["max"]
    rng = np.random.default_rng(0)
    warm = [Request(prompt=rng.integers(gen.FIRST_TOKEN_ID, conf["vocab_size"],
                                        size=min(b, top), dtype=np.int32),
                    max_new_tokens=2, task_id=0)
            for b in range(bucket, -(-top // bucket) * bucket + 1, bucket)]
    sched.run(warm)

    lead, seconds = tr["lead_in_s"], ctx.seconds
    plan = gen.open_loop(tr, ctx.seed, [lead, seconds, tr["drain_max_s"]],
                         conf["vocab_size"])
    ctx.log(common.settle_heap())
    t0 = clock()
    w0, w1 = t0 + lead, t0 + lead + seconds
    if ctx.trace:
        stretch = Stretch(tempfile.mkdtemp(prefix="bench-trace-"),
                          w0 + tr["trace"]["start_frac"] * seconds,
                          tr["trace"]["length_s"])
    order = [Req(p, t0 + p.due_s) for p in plan]
    due_in_window = [r for r in order if w0 <= r.due < w1]
    nxt = 0
    ticks = 0
    tick_s = 0.0
    longest = (0.0, w0)      # the window's longest tick and its start
    compiled0 = ctx.compiles.snapshot()
    traces0 = sum(engine.trace_counts.values())
    lateness, backlog = [], {}
    while True:
        now = clock()
        for edge, at in (("start", w0), ("end", w1)):
            if edge not in backlog and now >= at:
                backlog[edge] = sched.pending
        while nxt < len(order) and order[nxt].due <= now:
            r = order[nxt]
            with jax.profiler.TraceAnnotation("submit"):
                rid = sched.submit(Request(prompt=r.plan.prompt,
                                           max_new_tokens=r.plan.max_new_tokens,
                                           task_id=r.plan.tenant))
            reqs[rid] = r
            r.submit = clock()
            lateness.append(r.submit - r.due)
            nxt += 1
        if now >= w1 and (all(r.last is not None and len(r.tokens) ==
                              r.plan.max_new_tokens for r in due_in_window)
                          or now >= w1 + tr["drain_max_s"]):
            break
        if stretch is not None:
            stretch.poll(now)
        if sched.active or sched.pending:
            a = clock()
            with jax.profiler.TraceAnnotation("step"):
                sched.step()
            b = clock()
            if w0 <= a < w1 and not (stretch is not None and stretch.active):
                ticks += 1
                tick_s += b - a
                longest = max(longest, (b - a, a))
        elif nxt < len(order):
            time.sleep(max(0.0, min(order[nxt].due - clock(), 0.01)))
        else:
            break
    if stretch is not None:
        stretch.stop()
    compiled1 = ctx.compiles.snapshot()
    traces1 = sum(engine.trace_counts.values())
    setup_s = w0 - ctx.t_start

    # end-to-end numbers from raw per-request samples
    inf = float("inf")
    ttft, tpot = [], []
    for r in due_in_window:
        done = len(r.tokens) == r.plan.max_new_tokens
        ttft.append(r.first - r.due if r.first is not None else inf)
        tpot.append((r.last - r.first) / (len(r.tokens) - 1) if done else inf)
    # the FLOPs the window's tokens need, over the whole window even in a
    # traced run: arrivals are open-loop, so a profiler stall delays work
    # that the window still completes (its backlog is empty at the end)
    window_tokens, window_flops = 0, 0
    for r in order:
        S = len(r.plan.prompt)
        for j, t in enumerate(r.times):
            if not w0 <= t < w1:
                continue
            # token 0 comes with the prefill of the prompt; token j >= 1
            # from the decode step that wrote position S + j - 1
            if j == 0:
                window_tokens += S + 1
                window_flops += model.prefill_flops(S)
            else:
                window_tokens += 1
                window_flops += model.decode_token_flops(S + j - 1)
    failed = sum(1 for r in due_in_window
                 if len(r.tokens) < r.plan.max_new_tokens)
    e2e = {
        "ttft_p95_ms": common.percentile(ttft, 95) * 1e3,
        "tpot_p95_ms": common.percentile(tpot, 95) * 1e3,
        "serve_tok_s": window_tokens / seconds,
        "setup_s": setup_s,
    }
    itl = [b - a for r in due_in_window for a, b in zip(r.times, r.times[1:])]
    ctx.log("ttft p50/p90/p95 ms " + " ".join(
        f"{common.percentile(ttft, q) * 1e3:.3f}" for q in (50, 90, 95))
        + "; tpot p50/p90/p95 ms " + " ".join(
        f"{common.percentile(tpot, q) * 1e3:.3f}" for q in (50, 90, 95))
        + f"; gaps between tokens p50/p95/p99 ms over {len(itl)} " + " ".join(
        f"{common.percentile(itl, q) * 1e3:.3f}" for q in (50, 95, 99)))
    ctx.log(f"requests due in the window {len(due_in_window)}, unfinished "
            f"{failed}; generator lateness p50 "
            f"{common.percentile(lateness, 50) * 1e3:.3f} ms, max "
            f"{max(lateness) * 1e3:.3f} ms over {len(lateness)} submits; "
            f"ticks in window {ticks}; queued at the window's start "
            f"{backlog.get('start')}, at its end {backlog.get('end')}")
    ctx.log(f"longest tick in the window {longest[0] * 1e3:.3f} ms, "
            f"{longest[1] - w0:.3f} s in; engine traces in the window "
            f"{traces1 - traces0}; " + ctx.gc_passes.summary(w0, w1)
            + f"; needed FLOP/s over the window {window_flops / seconds!r}")
    ctx.log(f"set-up: programs compiled {compiled0[0]}, loaded from the "
            f"persistent cache {compiled0[1]}")
    ctx.log(f"programs compiled inside the window: "
            f"{compiled1[0] - compiled0[0]}, loaded from the cache: "
            f"{compiled1[1] - compiled0[1]}")
    memory = common.memory_peak_bytes(ctx.chips)
    trace = stretch.read() if stretch is not None else {}

    # free the program's state before the reference runs
    finished = [(r.plan.prompt, np.asarray(r.tokens, np.int32), r.plan.tenant)
                for r in due_in_window
                if len(r.tokens) == r.plan.max_new_tokens]
    del sched, engine, prefill, decode, traced_prefill, traced_decode
    gc.collect()

    chk = tr["check"]
    sample = pick_sample(finished, ctx.seed, chk["served_tokens"],
                         chk["max_requests"])
    gaps, control = ctx.arch.served_gaps(
        conf, layout, common.jax_key(ctx.seed, "weights"),
        conf["initializer_range"], sample, length=max_len,
        max_new=tr["output_len"]["max"], control=ctx.control)
    numbers = {}
    for who, g in (("program", gaps), ("control", control)):
        if g is None:
            continue
        every = np.concatenate(g) if g else np.full((1,), inf)
        numbers = {"widest_logit_gap": float(every.max()),
                   "mean_logit_gap": float(every.mean())}
        ctx.log(f"{who} over {len(sample)} requests, {every.size} served "
                f"tokens, {int(np.count_nonzero(every))} not the reference's "
                f"first: " + ", ".join(f"{k} {v!r}"
                                       for k, v in numbers.items()))
    # a request still streaming when the drain ends is late, not wrong;
    # one that never produced a token never came
    silent = sum(1 for r in due_in_window if r.first is None)
    checks = [[k, numbers[k], limit] for k, limit in chk["limits"].items()]
    checks.append(["requests_never_answered", silent, 0])
    return {
        "e2e": e2e,
        "attempted": len(due_in_window),
        "failed": failed,
        "checks": checks,
        "correct": all(v <= limit for _, v, limit in checks),
        "memory_peak_bytes": memory,
        "trace": trace,
        "record": {
            "counts": model,
            "window_s": seconds,
            "ticks": ticks,
            "tick_s": tick_s,
            "window_flops": window_flops,
            "decode_positions": in_stretch["decode"],
            "prefill_lengths": in_stretch["prefill"],
        },
    }


def pick_sample(finished, seed: int, served_tokens: int, max_requests: int):
    """The finished request with the most served tokens and the one with
    the longest prompt + output, then others in a seeded order until
    `served_tokens` are covered (at most `max_requests`)."""
    if not finished:
        return []
    by_out = max(range(len(finished)), key=lambda i: len(finished[i][1]))
    by_all = max(range(len(finished)),
                 key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    picked = list(dict.fromkeys([by_out, by_all]))
    rest = [i for i in np.random.default_rng(seed).permutation(len(finished))
            if i not in picked]
    while rest and len(picked) < max_requests and \
            sum(len(finished[i][1]) for i in picked) < served_tokens:
        picked.append(int(rest.pop(0)))
    return [finished[i] for i in picked]
