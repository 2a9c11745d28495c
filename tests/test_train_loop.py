"""Training-loop operational fixes: watchdog baseline clamping, per-config
eval-step memoization, cadence-only metric materialization in run_train,
and the pretrain disk-cache tag keying every trajectory-relevant knob.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np

from conftest import tiny_cfg
from repro.common.types import OptimCfg
from repro.models import model as M
from repro.train import loop
from repro.train.loop import StepWatchdog, evaluate, run_train
from repro.train.pretrain import pretrain_encoder, pretrain_tag

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# StepWatchdog
# ---------------------------------------------------------------------------


def test_watchdog_flags_straggler_and_keeps_baseline():
    wd = StepWatchdog(factor=2.0, alpha=0.1)
    assert wd.observe(0, 1.0) is False  # first sample seeds the EWMA
    assert wd.observe(1, 1.0) is False
    assert wd.observe(2, 10.0) is True
    assert wd.stragglers[0][0] == 2


def test_watchdog_clamp_keeps_flagging_a_straggler_run():
    """A run of consecutive stragglers must stay flagged: folding the raw
    straggler samples into the EWMA used to raise the detection threshold
    past the pathology after a handful of steps (10.0 > 2*ewma stopped
    holding by the 6th straggler with alpha=0.1)."""
    wd = StepWatchdog(factor=2.0, alpha=0.1)
    for i in range(5):
        wd.observe(i, 1.0)
    flags = [wd.observe(5 + j, 10.0) for j in range(9)]
    assert all(flags), flags
    # the baseline may drift up, but only through the clamped updates
    assert wd.ewma < 5.0


def test_watchdog_keeps_a_bounded_record():
    """A serving process watches ticks forever: the straggler record keeps
    the newest 1024 flags. The loop's import is the obs class."""
    from repro.obs import StepWatchdog as ObsWatchdog

    assert StepWatchdog is ObsWatchdog
    wd = StepWatchdog(factor=2.0, alpha=0.1)
    wd.observe(0, 1.0)
    flagged = []
    for i in range(1, 1101):  # a normal step before each straggler
        wd.observe(2 * i - 1, 1.0)
        if wd.observe(2 * i, 100.0):
            flagged.append(2 * i)
    assert len(flagged) == 1100
    assert len(wd.stragglers) == 1024
    assert [s[0] for s in wd.stragglers] == flagged[-1024:]


# ---------------------------------------------------------------------------
# evaluate memoization
# ---------------------------------------------------------------------------


def test_evaluate_builds_eval_step_once_per_config(monkeypatch):
    loop._jitted_eval_step.cache_clear()
    calls = []
    orig = loop.build_eval_step

    def counting(cfg):
        calls.append(cfg.name)
        return orig(cfg)

    monkeypatch.setattr(loop, "build_eval_step", counting)
    try:
        cfg = tiny_cfg()
        params = M.init_params(KEY, cfg)
        rs = np.random.RandomState(0)
        batches = [{"tokens": rs.randint(0, 97, (2, 8)).astype(np.int32),
                    "labels": rs.randint(0, 97, (2, 8)).astype(np.int32)}]
        for _ in range(3):
            evaluate(cfg, params, batches)
        assert len(calls) == 1  # memoized: one build/jit across evals

        cfg2 = tiny_cfg(d_ff=96)
        evaluate(cfg2, M.init_params(KEY, cfg2), batches)
        assert len(calls) == 2  # a new config still gets its own step
    finally:
        loop._jitted_eval_step.cache_clear()


# ---------------------------------------------------------------------------
# run_train metric materialization cadence
# ---------------------------------------------------------------------------


def test_run_train_materializes_metrics_at_cadence_only(monkeypatch):
    """The hot loop must not force a device->host sync per step: during the
    run only the log_every steps materialize (1 call at the first log, not
    5), the rest are converted in bulk after the loop, and every step is
    converted exactly once."""
    n_host = [0]
    orig = loop._host_metrics

    def counting(m):
        n_host[0] += 1
        return orig(m)

    monkeypatch.setattr(loop, "_host_metrics", counting)

    def step(state, batch):
        s = state["step"] + 1
        return {"step": s}, {"loss": s.astype(jnp.float32),
                             "grad_norm": jnp.float32(0.0)}

    at_log = []
    state = {"step": jnp.zeros((), jnp.int32)}
    batches = ({"x": np.zeros(1, np.float32)} for _ in range(10))
    state, hist = run_train(
        state, step, batches, steps=10, log_every=5,
        log=lambda msg: at_log.append(n_host[0]) if "step" in msg else None)

    assert at_log == [1, 2]  # per-step sync would read [5, 10]
    assert n_host[0] == 10  # each step exactly once (no double transfer)
    assert [h["loss"] for h in hist] == [float(i + 1) for i in range(10)]
    assert all(isinstance(h["loss"], float) for h in hist)


def test_run_train_history_is_host_floats_without_logging():
    def step(state, batch):
        return state, {"loss": jnp.float32(1.5), "grad_norm": jnp.float32(0)}

    _, hist = run_train({"step": jnp.zeros((), jnp.int32)}, step,
                        ({} for _ in range(3)), steps=3)
    assert [h["loss"] for h in hist] == [1.5, 1.5, 1.5]
    assert all(isinstance(h["loss"], float) for h in hist)


# ---------------------------------------------------------------------------
# pretrain cache tag
# ---------------------------------------------------------------------------


def test_pretrain_tag_keys_every_trajectory_knob():
    cfg = tiny_cfg()
    base = dict(steps=10, batch=4, seq=16, lr=1e-3, mask_rate=0.15, seed=0)
    t0 = pretrain_tag(cfg, **base)
    assert t0 != pretrain_tag(cfg, **dict(base, lr=2e-3))
    assert t0 != pretrain_tag(cfg, **dict(base, mask_rate=0.3))
    assert t0 != pretrain_tag(cfg, **dict(base, seed=1))
    # quantized moments alter the trajectory -> key the cache too
    qt = pretrain_tag(cfg, **base,
                      optim=OptimCfg(m_dtype="bfloat16", v_dtype="int8"))
    assert qt != t0 and "bfloat16" in qt
    assert pretrain_tag(cfg, **base, optim=OptimCfg()) == t0


def test_pretrain_encoder_cache_distinguishes_lr(tmp_path):
    """Regression: the cache key used to omit lr/mask_rate, silently
    reusing a stale backbone when either changed."""
    cfg = tiny_cfg()
    kw = dict(steps=3, batch=2, seq=16, cache_dir=str(tmp_path),
              log=lambda *_: None)
    pretrain_encoder(cfg, lr=1e-3, **kw)
    pretrain_encoder(cfg, lr=2e-3, **kw)
    pretrain_encoder(cfg, lr=1e-3, mask_rate=0.4, **kw)
    assert len(os.listdir(tmp_path)) == 3
    pretrain_encoder(cfg, lr=1e-3, **kw)  # cache hit: no fourth file
    assert len(os.listdir(tmp_path)) == 3
