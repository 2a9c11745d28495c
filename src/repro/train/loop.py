"""Training loops: single-stage runner, the paper's two-stage recipe, and
operational hooks (checkpointing cadence, straggler watchdog).
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import tree as tu
from repro.common.types import ModelCfg, OptimCfg, TrainCfg
from repro.core import peft
from repro.models import model as M
from repro.obs.watchdog import StepWatchdog
from repro.train import metrics as metrics_mod
from repro.train.steps import build_eval_step, build_train_step, make_state, merged_params


def _host_metrics(m: Dict) -> Dict[str, float]:
    """Materialize one step's metric dict on the host (device sync)."""
    return {k: float(v) for k, v in m.items()}


def run_train(state, step_fn, batches: Iterable, *, steps: int,
              log_every: int = 0, manager=None, save_every: int = 0,
              watchdog: Optional[StepWatchdog] = None,
              log: Callable[[str], None] = print, obs=None):
    """Generic jit'd training loop. Returns (state, history).

    Metrics stay on device in the hot loop: forcing them to host floats
    every step blocks on the step's completion and serializes dispatch
    (the next step cannot be enqueued while the host waits on the
    transfer). They are materialized only at the `log_every` cadence and
    once more, in bulk, after the loop - history is returned as plain
    float dicts either way. With a `watchdog` the loop *does* block every
    step, on purpose: straggler detection needs the step's own wall time,
    not the microseconds of an async dispatch.

    obs: optional `repro.obs.MetricsRegistry`. Step wall time lands in the
    `train_step_s` histogram only under a watchdog (same reason as above:
    timing an async dispatch would be meaningless); straggler flags and
    optimizer-state size are recorded whenever `obs` is given.
    """
    jstep = jax.jit(step_fn, donate_argnums=(0,))
    h_step = obs.histogram("train_step_s") if obs is not None else None
    if obs is not None and "opt" in state:
        obs.gauge("train_opt_state_bytes").set(tu.tree_bytes(state["opt"]))
    history = []
    hosted: Dict[int, Dict[str, float]] = {}  # i -> cadence-materialized
    it = iter(batches)
    for i in range(steps):
        batch = next(it)
        t0 = time.perf_counter()
        state, m = jstep(state, batch)
        if watchdog is not None:
            # barrier first: dt must time the step, not the dispatch (nor
            # a later transfer that drains the previous step's queue)
            jax.block_until_ready(m)
            dt = time.perf_counter() - t0
            if h_step is not None:
                h_step.observe(dt)
            if watchdog.observe(i, dt):
                if obs is not None:
                    obs.counter("train_straggler_steps_total").inc()
                    obs.event("straggler", step=i, dt_s=dt,
                              ewma_s=watchdog.ewma)
                log(f"[watchdog] straggler step {i}: {dt:.3f}s "
                    f"(ewma {watchdog.ewma:.3f}s)")
        history.append(m)
        if log_every and (i + 1) % log_every == 0:
            hm = hosted[i] = _host_metrics(m)
            log(f"step {i+1}/{steps} loss={hm['loss']:.4f} "
                f"gnorm={hm['grad_norm']:.3f}")
        if manager is not None and save_every and (i + 1) % save_every == 0:
            manager.save(int(state["step"]), state)
    history = [hosted[i] if i in hosted else _host_metrics(m)
               for i, m in enumerate(history)]
    return state, history


@functools.lru_cache(maxsize=None)
def _jitted_eval_step(cfg: ModelCfg):
    """One jitted eval step per config: `evaluate` used to wrap
    `build_eval_step` in a fresh `jax.jit` on every call, retracing per
    eval - the sparse ablation loop calls it once per layer. ModelCfg is
    a frozen (hashable) dataclass, so the jit wrapper - and with it jax's
    own trace cache - is memoized per config."""
    return jax.jit(build_eval_step(cfg))


def evaluate(cfg: ModelCfg, params, eval_batches, metric: str = "acc") -> float:
    ev = _jitted_eval_step(cfg)
    preds, labels = [], []
    for batch in eval_batches:
        preds.append(np.asarray(ev(params, batch)))
        labels.append(np.asarray(batch["labels"]))
    return metrics_mod.metric_fn(metric)(
        np.concatenate(preds), np.concatenate(labels))


def overlay_by_path(dst, src):
    """Copy every leaf of src into dst where paths coincide (stage-1 head
    reload into the stage-2 tree, which additionally contains adapters)."""
    src_leaves = dict(tu.flatten_with_paths(src))

    def pick(path, v):
        return src_leaves.get(path, v)

    return tu.map_with_path(pick, dst)


def two_stage_finetune(
    key,
    base_cfg: ModelCfg,
    strategy_name: str,
    data,  # object with .train_batches(n, bs, seed) and .eval_batches(bs)
    *,
    stage1: TrainCfg,
    stage2: TrainCfg,
    metric: str = "acc",
    pretrained_params=None,
    layer_mask=None,
    log: Callable[[str], None] = print,
) -> Dict:
    """The paper's recipe (§3.2). Returns dict with params, metrics, stats.

    layer_mask: optional (n_layers,) bool mask (repro.sparse) gating
    stage-2 gradients - adapters of masked-off layers stay identity, the
    paper's pruned 0.022% variant trained from the start. Reported
    param_stats then count only the surviving layers."""
    strat = peft.strategy(strategy_name)

    # ---- stage 1: classifier only, no adapter in the tree ----
    cfg1 = peft.attach(base_cfg, peft.strategy("classifier_only"))
    k1, k2 = jax.random.split(key)
    params1 = pretrained_params if pretrained_params is not None \
        else M.init_params(k1, cfg1)
    state1 = make_state(k1, cfg1, peft.strategy("classifier_only"),
                        stage1.optim, params=params1)
    step1 = build_train_step(cfg1, stage1.optim, microbatch=stage1.microbatch)
    state1, hist1 = run_train(
        state1, step1, data.train_batches(stage1.steps, stage1.batch_size,
                                          seed=stage1.seed),
        steps=stage1.steps, log_every=stage1.log_every, log=log)
    params1 = merged_params(state1)
    m1 = evaluate(cfg1, params1, data.eval_batches(stage1.batch_size), metric)
    log(f"[stage1] classifier-only {metric}={m1:.4f}")

    if not strat.two_stage:
        return {"params": params1, "stage1_metric": m1, "final_metric": m1,
                "cfg": cfg1}

    # ---- stage 2: inject adapter, reload head, tune adapter + norms ----
    cfg2 = peft.attach(base_cfg, strat)
    params2 = M.init_params(k2, cfg2)  # fresh tree containing adapters
    params2 = overlay_by_path(params2, params1)  # backbone + trained head
    state2 = make_state(k2, cfg2, strat, stage2.optim, params=params2)
    step2 = build_train_step(cfg2, stage2.optim, microbatch=stage2.microbatch,
                             layer_mask=layer_mask)
    state2, hist2 = run_train(
        state2, step2, data.train_batches(stage2.steps, stage2.batch_size,
                                          seed=stage2.seed + 1),
        steps=stage2.steps, log_every=stage2.log_every, log=log)
    params2 = merged_params(state2)
    m2 = evaluate(cfg2, params2, data.eval_batches(stage2.batch_size), metric)

    mask = peft.trainable_mask(params2, strat, stage=2)
    stats = peft.param_stats(params2, mask)
    if layer_mask is not None:
        from repro.sparse.importance import gated_param_count, mask_gate

        n = gated_param_count(params2, mask,
                              mask_gate(params2, cfg2, layer_mask))
        stats = dict(stats, trainable=n,
                     fraction=n / max(stats["total"], 1),
                     percent=100.0 * n / max(stats["total"], 1))
    log(f"[stage2] {strategy_name} {metric}={m2:.4f} "
        f"trainable={stats['trainable']} ({stats['percent']:.4f}%)")
    return {
        "params": params2,
        "cfg": cfg2,
        "stage1_metric": m1,
        "final_metric": m2,
        "param_stats": stats,
        "history": {"stage1": hist1, "stage2": hist2},
    }
