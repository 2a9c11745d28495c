"""Hadamard-adapter training of an encoder classifier through the
program's `make_state` and `build_train_step` (the paper's stage 2:
adapter w, b and the FFN-output norm train, the backbone is frozen).

Set-up builds the state from the seed's weights and jits the step once,
donating the state as `train/loop.run_train` does; that one jitted step
runs the first three steps (rows that all differ, from a seeded
permutation of the task's synthetic training set, each batch's rows in
order of label) and then the window.
`run_train` itself is not called: it wraps the step in a fresh `jax.jit`
on every call, so a window driven through it would build its program
again inside the window. The window dispatches asynchronously, keeping
at most two steps in flight, with the device synced at both ends.

Correct: the architecture's plain float32 reference (its module's
`train_reference`) runs the same three steps from the same weights. The
numbers, each the worst over the trainable leaves, with the leaves whose
reference gradient is under a thousandth of the median leaf's left out
of the last two: the first gradient (Adam's first moment after step 1,
over 1 - b1) by | |g| - |g_ref| | over max(|g_ref|, the median leaf's
|g_ref|) and by 1 - cos(g, g_ref); the change of each leaf over the
three steps by the same norm gap; and each step's relative loss gap.
The traffic file's `check` block names the ones compared and their
limits; every number is printed. The control (`--control`) is the
program on its own bfloat16 path (the traffic file's `control` block),
against the float32 reference.
"""
from __future__ import annotations

import collections
import gc
import tempfile
import time

import numpy as np

from bench import common, weights
from bench.trace import Stretch


def leaves(tree) -> dict:
    """path -> host float32 array for the non-None leaves of a tree."""
    import jax

    return {p: np.asarray(v, np.float32)
            for p, v in weights.flatten(jax.device_get(tree)).items()
            if v is not None}


def relative_norm_gaps(got: dict, want: dict, keep=None) -> dict:
    """Per leaf: | |got| - |want| | over max(|want|, median leaf |want|)."""
    norms = {p: float(np.linalg.norm(want[p])) for p in want}
    floor = float(np.median(list(norms.values())))
    return {p: abs(float(np.linalg.norm(got[p])) - norms[p])
            / max(norms[p], floor, 1e-30)
            for p in want if keep is None or p in keep}


def cosine_gap(got, want) -> float:
    """1 - cos(got, want); 1 where got is all zero."""
    n = float(np.linalg.norm(got)) * float(np.linalg.norm(want))
    return 1.0 - float(np.vdot(got, want)) / n if n else 1.0


def run(ctx) -> dict:
    import jax
    from repro.common.types import OptimCfg
    from repro.core import peft
    from repro.data.synthetic import TaskData
    from repro.train import steps as train_steps

    conf = stated = ctx.config
    tr = ctx.traffic
    if ctx.control:
        conf = dict(conf, dtype=dict(conf["dtype"], **tr["control"]["dtype"]))
    arch = ctx.arch
    cfg = arch.program_cfg(conf)
    layout = arch.layout(conf)
    weights.check_layout(layout, weights.program_shapes(cfg))
    key = common.jax_key(ctx.seed, "weights")
    o = tr["optim"]
    ocfg = OptimCfg(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=0.0, grad_clip=o["grad_clip"],
                    schedule="constant", warmup_steps=0)
    state = train_steps.make_state(
        key, cfg, peft.strategy("hadamard"), ocfg,
        params=weights.make(key, layout, conf["initializer_range"]))
    jstep = jax.jit(train_steps.build_train_step(cfg, ocfg),
                    donate_argnums=(0,))

    B, S = tr["batch"], tr["seq"]
    data = TaskData(tr["task"], conf["vocab_size"], seq_len=S,
                    n_train=tr["rows"], n_eval=B, seed=ctx.seed)
    order = np.random.default_rng(ctx.seed).permutation(tr["rows"])
    per_epoch = tr["rows"] // B

    def batch(i):
        """Rows of a batch in order of label: a sound step's mean over
        rows does not see the order, while a step that drops part of its
        batch trains on another mix of labels."""
        idx = order[(i % per_epoch) * B:(i % per_epoch + 1) * B]
        idx = idx[np.argsort(data.train["labels"][idx], kind="stable")]
        return {k: v[idx] for k, v in data.train.items()}

    # the first steps, read back for the comparison (set-up)
    p0 = leaves(state["trainable"])
    losses, norms, g1 = [], [], None
    for i in range(3):
        with jax.profiler.TraceAnnotation("train_step"):
            state, m = jstep(state, batch(i))
        losses.append(float(m["loss"]))
        norms.append(float(m["grad_norm"]))
        if i == 0:
            g1 = {p: v / (1 - ocfg.b1) for p, v in
                  leaves(state["opt"]["m"]).items()}
    change = {p: v - p0[p] for p, v in leaves(state["trainable"]).items()}

    stretch = None
    clock = time.perf_counter
    jax.block_until_ready(state)
    ctx.log(common.settle_heap())
    t0 = clock()
    if ctx.trace:
        stretch = Stretch(tempfile.mkdtemp(prefix="bench-trace-"),
                          t0 + tr["trace"]["start_frac"] * ctx.seconds,
                          tr["trace"]["length_s"])
    compiled0 = ctx.compiles.snapshot()
    n, window_losses, in_flight = 0, [], collections.deque()
    done_at = []  # host clock as each step's loss is found ready
    while True:
        with jax.profiler.TraceAnnotation("train_step"):
            state, m = jstep(state, batch(3 + n))
        n += 1
        window_losses.append(m["loss"])
        in_flight.append(m["loss"])
        if len(in_flight) > 2:
            with jax.profiler.TraceAnnotation("wait"):
                in_flight.popleft().block_until_ready()
            done_at.append(clock())
        now = clock()
        if stretch is not None:
            stretch.poll(now)
        if now - t0 >= ctx.seconds:
            break
    with jax.profiler.TraceAnnotation("sync"):
        jax.block_until_ready(state)
    t1 = clock()
    if stretch is not None:
        stretch.stop()
    compiled1 = ctx.compiles.snapshot()
    ctx.log(f"set-up: programs compiled {compiled0[0]}, loaded from the "
            f"persistent cache {compiled0[1]}")
    ctx.log(f"programs compiled inside the window: "
            f"{compiled1[0] - compiled0[0]}, loaded from the cache: "
            f"{compiled1[1] - compiled0[1]}")
    window_s = t1 - t0
    bad = int(np.sum(~np.isfinite(np.asarray(jax.device_get(window_losses)))))
    # the steady rate outside the traced stretch (all of the window in an
    # untraced run): steps between the first and last completion seen in
    # each part, over the time between them
    outside_steps, outside_s = 0, 0.0
    for a, b in (stretch.outside(t0, t1) if stretch is not None
                 else [(t0, t1)]):
        seen = [t for t in done_at if a <= t < b]
        if len(seen) > 1:
            outside_steps += len(seen) - 1
            outside_s += seen[-1] - seen[0]
    flops_tok = arch.Counts(conf).train_token_flops(S)
    gap = max(zip(np.diff(done_at), done_at[1:]), default=(0.0, t0))
    ctx.log(f"{n} steps in {window_s:.6f} s; non-finite losses {bad}; "
            f"{outside_steps} steps in {outside_s:.6f} s between completions "
            f"outside the traced stretch, needed FLOP/s "
            f"{outside_steps * B * S * flops_tok / max(outside_s, 1e-9)!r}; "
            f"longest time between completions {gap[0] * 1e3:.3f} ms, "
            f"{gap[1] - t0:.3f} s in; " + ctx.gc_passes.summary(t0, t1))
    memory = common.memory_peak_bytes(ctx.chips)
    trace = stretch.read() if stretch is not None else {}
    del state, jstep, m, in_flight, window_losses
    gc.collect()

    ref_losses, ref_g1, ref_change = arch.train_reference(
        stated, arch.layout(stated), key, stated["initializer_range"],
        [batch(i) for i in range(3)], o)
    gnorm = {p: float(np.linalg.norm(v)) for p, v in ref_g1.items()}
    moving = {p for p, v in gnorm.items()
              if v >= 1e-3 * float(np.median(list(gnorm.values())))}
    numbers = {
        "grad_norm_gap": max(relative_norm_gaps(g1, ref_g1).values()),
        "grad_cosine_gap": max(cosine_gap(g1[p], ref_g1[p]) for p in moving),
        "change_norm_gap": max(relative_norm_gaps(
            change, ref_change, keep=moving).values()),
    }
    for i, (a, b) in enumerate(zip(losses, ref_losses), start=1):
        numbers[f"loss_gap_step{i}"] = abs(a - b) / abs(b)
    ctx.log(f"losses {losses} reference {ref_losses}; gradient norms "
            f"before clipping {norms}")
    ctx.log("numbers: " + ", ".join(f"{k} {v!r}" for k, v in numbers.items()))
    checks = [[k, numbers[k], limit] for k, limit in tr["check"].items()]
    checks.append(["nonfinite_losses", bad, 0])
    return {
        "e2e": {"train_tok_s": n * B * S / window_s,
                "setup_s": t0 - ctx.t_start},
        "attempted": n,
        "failed": bad,
        "checks": checks,
        "correct": all(v <= limit for _, v, limit in checks),
        "memory_peak_bytes": memory,
        "trace": trace,
        "record": {"outside_steps": outside_steps,
                   "outside_s": outside_s, "tokens_per_step": B * S,
                   "flops_per_token": flops_tok},
    }
