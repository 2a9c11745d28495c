"""Chip smoke test: drive the serve and train paths once on a TPU, at the
full published width of the repo's models, through the same entry points
the launchers use.

  python chip_smoke.py             # one chip: serve, int8 kernel, train
  python chip_smoke.py --chips 4   # the sharded path on four chips, and the
                                   # one-device runs it is compared with

Phases (one process; any failed check stops the run):
  serve  qwen3-0.6b with a 3-task Hadamard bank behind `make_scheduler`:
         every request retires, greedy tokens equal `engine.generate`
         lock-step, no decode retrace, and the prefill's last-position
         logits agree with an fp32 forward on the host CPU.
  int8   the same engine with an int8 backbone: the compiled Pallas
         dequant-matmul is in the decode step, agrees with the jnp path,
         and serves requests to completion.
  train  bert-base (the paper's PLM), Hadamard strategy, synthetic SST-2:
         finite loss, frozen backbone bit-unchanged, adapter leaves moved.

Weights are random, from `--seed`. The run exits non-zero, printing no
result line, when JAX finds no TPU or any check fails. Otherwise the last
line of stdout is one JSON object:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

This is a smoke run, not a benchmark: its times include compilation and
one-off host work.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common import tree as tu  # noqa: E402
from repro.common.runtime import init_compile_cache  # noqa: E402
from repro.common.types import OptimCfg  # noqa: E402
from repro.configs import PAPER, get  # noqa: E402
from repro.core import peft  # noqa: E402
from repro.data.pipeline import shard_batches  # noqa: E402
from repro.data.synthetic import TaskData  # noqa: E402
from repro.dist.api import use_mesh  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import build_params  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.obs import MetricsRegistry  # noqa: E402
from repro.quant.qtensor import quantizable, quantize  # noqa: E402
from repro.serving import (MultiTaskEngine, Request, Scheduler,  # noqa: E402
                           ServingConfig, make_scheduler)
from repro.train.loop import StepWatchdog, run_train  # noqa: E402
from repro.train.steps import build_train_step, make_state  # noqa: E402

# Prefill logits (bf16 weights and compute, 28 layers) against an fp32
# forward of the same weights: bf16 keeps 8 significant bits, and the
# rounding of each layer compounds, so allow 2^-4 of the logit range.
# `logits_check` also shows that another task's adapter row falls outside
# this bound, so a routing fault cannot pass it.
LOGIT_TOL = 2.0 ** -4
# The int8 Pallas dequant-matmul against the jnp path on the same chip:
# both widen to fp32; the output is bf16 (2^-8 relative), and the MXU may
# take bf16 passes, so allow 2^-6 of the output range.
KERNEL_TOL = 2.0 ** -6
# One train step on a (2, 2) mesh against one device: each leaf's
# gradients differ by the order of sums and the bf16 passes of fp32
# matmuls on a TPU, 1.3e-3 of the leaf's largest on a v5e. A missing
# all-reduce over the data axis leaves each replica with its half batch's
# gradient (0.23 away on a v5e); `sharded_phase` shows that this lies
# outside 1% of the largest.
GRAD_TOL = 1e-2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class SmokeFailure(Exception):
    """A smoke check failed: `main` exits non-zero without the result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


class CompileClock:
    """While active, sums XLA backend-compile seconds (programs not found
    in the persistent cache) and counts programs loaded from the cache
    (jax.monitoring)."""

    def __enter__(self):
        self.seconds, self.cache_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __str__(self):
        return (f"compile {self.seconds:.3f} s, {self.cache_hits} programs "
                "from the persistent cache")

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
        elif event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def device_info(n_chips: int) -> dict:
    """The device JAX reports; raises unless it is a TPU with n_chips."""
    backend = jax.default_backend()
    dev = jax.devices()[0]
    if backend != "tpu" or dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's backend is {backend!r} "
                           f"(device {dev.platform!r})")
    if len(jax.devices()) < n_chips:
        raise SmokeFailure(f"{n_chips} chips wanted, "
                           f"{len(jax.devices())} found")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"device: {info['kind']} x{info['count']}; jax {jax.__version__}, "
          f"libtpu {importlib.metadata.version('libtpu')}", flush=True)
    return info


def peak_bytes() -> str:
    """The process's peak device memory so far (`peak_bytes_in_use`
    never resets, so a later phase reports at least an earlier one's)."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use']} bytes"


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_requests(cfg, n: int, prompt_len: int, new_tokens: int,
                  tasks: int, seed: int):
    """n requests over tasks round-robin. Prompt lengths cycle through four
    steps up to prompt_len (each length is one lock-step reference
    compile); budgets are staggered up to new_tokens as the launcher
    staggers them."""
    rs = np.random.RandomState(seed)
    lengths = [prompt_len * (k + 1) // 4 for k in range(4)]
    return [Request(prompt=rs.randint(10, cfg.vocab_size,
                                      size=(lengths[i % 4],)),
                    max_new_tokens=int(rs.randint(max(1, new_tokens // 2),
                                                  new_tokens + 1)),
                    task_id=i % tasks)
            for i in range(n)]


def serving_config(cfg, prompt_len: int, new_tokens: int, num_slots: int,
                   quant=None) -> ServingConfig:
    """The contiguous scheduler as `launch/serve.py` configures it."""
    bucket = 8 if Scheduler.supports_bucketing(cfg) else None
    return ServingConfig(num_slots=num_slots,
                         max_len=prompt_len + new_tokens,
                         prefill_bucket=bucket, backbone_quant=quant)


def serve(engine, reqs, scfg: ServingConfig):
    """Run reqs through make_scheduler twice (cold, then warm). Returns
    (completions, cold report, warm report, CompileClock)."""
    obs = MetricsRegistry()
    sched = make_scheduler(engine, scfg, obs=obs)
    with CompileClock() as clock:
        done, cold = sched.run(reqs)
    again, warm = sched.run(reqs)
    check(len(done) == len(reqs) and all(
        c.finish_reason == "length" and len(c.tokens) == r.max_new_tokens
        for c, r in zip(done, reqs)),
        f"all {len(reqs)} requests retired with their budgets")
    check(all(np.array_equal(a.tokens, b.tokens)
              for a, b in zip(done, again)),
          "a warm rerun reproduces every token")
    check(not obs.events_of("retrace"), "zero retrace events")
    return done, cold, warm, clock


def report(tag: str, cold: dict, warm: dict, clock: CompileClock) -> None:
    print(f"[{tag}] {clock}; cold run "
          f"{cold['elapsed_s']:.3f} s; warm run {warm['tokens']} tokens in "
          f"{warm['elapsed_s']:.3f} s = {warm['tokens_per_s']:.1f} tok/s "
          f"over {warm['ticks']} ticks; process peak device memory so far "
          f"{peak_bytes()}",
          flush=True)


def lockstep(engine, reqs):
    """engine.generate lock-step over same-length groups of reqs."""
    out = {}
    for n in sorted({len(r.prompt) for r in reqs}):
        idx = [i for i, r in enumerate(reqs) if len(r.prompt) == n]
        for i, toks in zip(idx, engine.generate([reqs[i] for i in idx])):
            out[i] = toks
    return [out[i] for i in range(len(reqs))]


def to_fp32_on(tree, device):
    with jax.default_device(device):
        return jax.tree.map(
            lambda a: jnp.asarray(a, jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else jnp.asarray(a),
            jax.device_put(tree, device))


def logits_check(engine, cfg, variants, req, scfg: ServingConfig) -> None:
    """The engine's prefill of one prompt (as the scheduler pads it)
    against an fp32 forward of its task's weights on the host CPU. The
    same forward with another task's weights must miss the bound, so the
    check tells a wrong adapter row from bf16 rounding."""
    prompt = np.asarray(req.prompt, np.int32)[None]
    S = prompt.shape[1]
    padded = prompt
    if scfg.prefill_bucket:
        padded = np.pad(prompt, ((0, 0), (0, -S % scfg.prefill_bucket)))
    logits, _ = engine.prefill(padded, scfg.max_len,
                               task_ids=np.asarray([req.task_id]),
                               last_pos=S - 1)
    got = np.asarray(logits[0, -1], np.float32)

    cpu = jax.devices("cpu")[0]
    cfg32 = cfg.replace(param_dtype="float32", compute_dtype="float32")
    fwd = jax.jit(lambda p, t: M.forward_lm(p, cfg32, t))
    tokens = jax.device_put(prompt, cpu)

    def reference(task):
        with jax.default_matmul_precision("highest"):
            ref, _ = fwd(to_fp32_on(variants[task], cpu), tokens)
        return np.asarray(ref[0, -1], np.float32)

    other = (req.task_id + 1) % len(variants)
    want, wrong = reference(req.task_id), reference(other)
    err = float(np.max(np.abs(got - want)))
    gap = float(np.max(np.abs(wrong - want)))
    scale = float(np.max(np.abs(want)))
    print(f"[serve] prefill logits of task {req.task_id} vs fp32 CPU: "
          f"max |err| {err:.6f}, max |ref| {scale:.6f}, top-1 "
          f"{int(got.argmax())} vs {int(want.argmax())}; task {other}'s "
          f"fp32 CPU logits differ by {gap:.6f}", flush=True)
    check(int(got.argmax()) == int(want.argmax()),
          "prefill top-1 equals the fp32 CPU forward's")
    check(err <= LOGIT_TOL * scale,
          f"prefill logits within {LOGIT_TOL} x max|ref| of fp32 CPU")
    check(gap > LOGIT_TOL * scale,
          "another task's logits lie outside that bound")


def serve_phase(cfg, *, requests: int = 8, prompt_len: int = 64,
                new_tokens: int = 16, num_slots: int = 4, tasks: int = 3,
                seed: int = 0) -> None:
    print(f"[serve] {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size} {cfg.param_dtype}, {tasks} tasks, "
          f"{requests} requests, {num_slots} slots", flush=True)
    _, variants = build_params(jax.random.PRNGKey(seed), cfg, tasks)
    engine = MultiTaskEngine(cfg, variants)
    reqs = make_requests(cfg, requests, prompt_len, new_tokens, tasks, seed)
    scfg = serving_config(cfg, prompt_len, new_tokens, num_slots)
    done, cold, warm, clock = serve(engine, reqs, scfg)
    want = lockstep(engine, reqs)
    check(all(np.array_equal(c.tokens, w) for c, w in zip(done, want)),
          "greedy completions token-identical to engine.generate lock-step")
    logits_check(engine, cfg, variants,
                 next(r for r in reqs if r.task_id != 0), scfg)
    report("serve", cold, warm, clock)


def decode_has_kernel(engine, num_slots: int, max_len: int) -> bool:
    """Whether the engine's jitted decode step lowers to a Pallas TPU
    kernel (`tpu_custom_call`)."""
    caches = engine.init_slot_caches(num_slots, max_len)
    z = jnp.zeros((num_slots,), jnp.int32)
    text = engine._decode_tasks.lower(engine.bank, caches, z[:, None], z,
                                      z).as_text()
    return "tpu_custom_call" in text


def int8_phase(cfg, *, requests: int = 4, prompt_len: int = 32,
               new_tokens: int = 8, num_slots: int = 4, tasks: int = 3,
               seed: int = 0) -> None:
    print(f"[int8] {cfg.name} with an int8 backbone", flush=True)
    _, variants = build_params(jax.random.PRNGKey(seed), cfg, tasks)
    engine = MultiTaskEngine(cfg, variants, quant="int8")
    scfg = serving_config(cfg, prompt_len, new_tokens, num_slots, "int8")
    check(decode_has_kernel(engine, num_slots, scfg.max_len),
          "decode step lowers to tpu_custom_call (dequant_matmul)")

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    x = jax.random.normal(k1, (num_slots, cfg.d_model), cfg.cdtype)
    qt = quantize(0.02 * jax.random.normal(k2, (cfg.d_model, cfg.d_ff)),
                  "int8")
    got = np.asarray(ops.dequant_matmul(x, qt.values, qt.scales,
                                        impl="pallas"), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ops.dequant_matmul(x, qt.values, qt.scales,
                                             impl="jnp"), np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    print("[int8] dequant_matmul pallas vs jnp at "
          f"({num_slots}, {cfg.d_model}) x ({cfg.d_model}, {cfg.d_ff}): "
          f"max |err| {err:.6f}, max |ref| {scale:.6f}", flush=True)
    check(err <= KERNEL_TOL * scale,
          f"dequant_matmul pallas within {KERNEL_TOL} x max|ref| of jnp")

    reqs = make_requests(cfg, requests, prompt_len, new_tokens, tasks, seed)
    report("int8", *serve(engine, reqs, scfg)[1:])


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def hadamard_setup(cfg, steps: int, seed: int):
    strat = peft.strategy("hadamard")
    cfg = peft.attach(cfg, strat)
    ocfg = OptimCfg(lr=3e-3, total_steps=steps)
    state = make_state(jax.random.PRNGKey(seed), cfg, strat, ocfg)
    return cfg, state, build_train_step(cfg, ocfg)


def train_phase(cfg, *, batch: int = 32, seq: int = 128, steps: int = 5,
                seed: int = 0) -> None:
    print(f"[train] {cfg.name}: {cfg.n_layers}L d={cfg.d_model}, hadamard, "
          f"synthetic sst2, batch {batch} x seq {seq}, {steps} steps",
          flush=True)
    cfg, state, step = hadamard_setup(cfg, steps, seed)
    frozen0 = jax.device_get(state["frozen"])
    trainable0 = jax.device_get(state["trainable"])
    data = TaskData("sst2", cfg.vocab_size, seq_len=seq, seed=seed)
    obs = MetricsRegistry()
    with CompileClock() as clock:
        state, hist = run_train(state, step,
                                data.train_batches(steps, batch, seed=seed),
                                steps=steps, watchdog=StepWatchdog(), obs=obs)
    losses = [h["loss"] for h in hist]
    print(f"[train] losses {losses}", flush=True)
    check(len(losses) == steps and all(np.isfinite(losses)),
          f"{steps} finite losses")

    def leaves(tree):
        return tu.flatten_with_paths(jax.device_get(tree))

    check(all(np.array_equal(a, b) for (_, a), (_, b) in
              zip(leaves(frozen0), leaves(state["frozen"]))),
          "frozen backbone bit-unchanged")
    moved = {p: float(np.max(np.abs(a - b))) for (p, a), (_, b) in
             zip(leaves(trainable0), leaves(state["trainable"]))
             if "/adapter/" in p}
    check(bool(moved) and all(d > 0 for d in moved.values()),
          f"all {len(moved)} adapter leaves moved")
    # step times as the watchdog measures them (each ends in a barrier);
    # the first step carries the compile or the cache load
    h = obs.histogram("train_step_s")
    print(f"[train] {clock}; step time first {h.max:.4f} s, fastest "
          f"{h.min:.4f} s = {batch * seq / h.min:.1f} tok/s; process peak "
          f"device memory so far {peak_bytes()}", flush=True)


# ---------------------------------------------------------------------------
# the sharded path (four chips)
# ---------------------------------------------------------------------------


def one_step(cfg, batches, seed: int):
    """One Hadamard train step from a fresh state through `run_train`.
    Returns (loss, gradient, update), the last two as lists of trainable
    leaves. The gradient is Adam's first moment after one step, which is
    (1 - b1) times the step's clipped gradient."""
    _, state, step = hadamard_setup(cfg, 1, seed)
    before = tu.flatten_with_paths(jax.device_get(state["trainable"]))
    state, hist = run_train(state, step, batches, steps=1)
    after = tu.flatten_with_paths(jax.device_get(state["trainable"]))
    grad = tu.flatten_with_paths(jax.device_get(state["opt"]["m"]))
    return (hist[0]["loss"], [m / (1 - OptimCfg.b1) for _, m in grad],
            [a - b for (_, a), (_, b) in zip(after, before)])


def sharded_phase(serve_cfg, train_cfg, *, n_devices: int = 4,
                  requests: int = 4, prompt_len: int = 32,
                  new_tokens: int = 8, tasks: int = 3, batch: int = 32,
                  seq: int = 128, seed: int = 0) -> None:
    print(f"[sharded] {serve_cfg.name} on a (1, {n_devices}) mesh, "
          f"{train_cfg.name} on a (2, {n_devices // 2}) mesh", flush=True)
    _, variants = build_params(jax.random.PRNGKey(seed), serve_cfg, tasks)
    reqs = make_requests(serve_cfg, requests, prompt_len, new_tokens, tasks,
                         seed)
    scfg = serving_config(serve_cfg, prompt_len, new_tokens, n_devices)
    want, *stats = serve(MultiTaskEngine(serve_cfg, variants), reqs, scfg)
    report("one-device serve", *stats)

    mesh = make_host_mesh(1, n_devices)
    with use_mesh(mesh):
        engine = MultiTaskEngine(serve_cfg, variants)
    split = {}
    for path, leaf in tu.flatten_with_paths(engine.bank):
        if quantizable(path):
            shards = leaf.addressable_shards
            split[path] = (len({s.device for s in shards}) == n_devices
                           and all(s.data.shape != leaf.shape
                                   for s in shards))
    check(bool(split) and all(split.values()),
          f"all {len(split)} backbone matmul leaves split over "
          f"{n_devices} devices")
    got, *stats = serve(engine, reqs, scfg)
    check(all(np.array_equal(a.tokens, b.tokens) for a, b in zip(got, want)),
          "sharded greedy tokens equal the one-device engine's")
    report("sharded serve", *stats)

    data = TaskData("sst2", train_cfg.vocab_size, seq_len=seq, seed=seed)
    batch0 = next(data.train_batches(1, batch, seed=seed))
    half = jax.tree.map(lambda x: x[:batch // 2], batch0)
    l1, g1, u1 = one_step(train_cfg, iter([batch0]), seed)
    _, gh, _ = one_step(train_cfg, iter([half]), seed)
    mesh2 = make_host_mesh(2, n_devices // 2)
    with use_mesh(mesh2):
        l2, g2, u2 = one_step(train_cfg,
                              shard_batches(iter([batch0]), mesh2), seed)
    print(f"[sharded] train loss one device {l1:.6f}, "
          f"(2, {n_devices // 2}) mesh {l2:.6f}", flush=True)
    check(np.isfinite(l2) and abs(l1 - l2) <= 1e-3 * max(1.0, abs(l1)),
          "SPMD step loss matches the one-device step within 1e-3")

    # the loss comes from the parameters before the update; the gradient
    # shows whether it was reduced over the data axis
    tops = [float(np.max(np.abs(g))) for g in g1]
    check(all(t > 0 for t in tops), f"all {len(g1)} trainable leaves have "
          "a gradient")

    def rel(other):
        """max |g - other| / max |g|, the worst over leaves"""
        return max(float(np.max(np.abs(g - o))) / t
                   for g, o, t in zip(g1, other, tops))

    err, gap = rel(g2), rel(gh)
    # Adam's first update is near lr * sign(g): where rounding flips the
    # sign of a near-zero gradient, the update moves by up to 2 lr
    lr = max(float(np.max(np.abs(u))) for u in u1)
    flipped = [np.abs(g)[np.abs(a - b) > lr / 2] / t
               for g, a, b, t in zip(g1, u1, u2, tops)]
    print(f"[sharded] train gradients, worst of {len(g1)} leaves relative "
          f"to the leaf's max |g|: SPMD vs one device {err:.6e}, half batch "
          f"vs full batch {gap:.6e}; {sum(f.size for f in flipped)} of "
          f"{sum(u.size for u in u1)} updates differ by over lr/2 (max "
          f"update {lr:.6e}), at |g| <= "
          f"{max((float(f.max()) for f in flipped if f.size), default=0.0):.6e}"
          " of the leaf's max", flush=True)
    check(err <= GRAD_TOL, f"SPMD step's gradient matches the one-device "
          f"step's within {GRAD_TOL} x max|g| per leaf")
    check(gap > GRAD_TOL, "a half batch's gradient (a missing all-reduce) "
          "lies outside that bound")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path and its one-device "
                         "reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        info = device_info(args.chips)
        print(f"compile cache: {init_compile_cache()}", flush=True)
        qwen = peft.attach(get("qwen3-0.6b"), peft.strategy("hadamard"))
        bert = PAPER["bert-base"]()
        if args.chips == 4:
            sharded_phase(qwen, bert, seed=args.seed)
        else:
            serve_phase(qwen, seed=args.seed)
            int8_phase(qwen, seed=args.seed)
            train_phase(bert, seed=args.seed)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        raise SystemExit(1)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
