"""Per-assigned-architecture smoke tests: a REDUCED config of the same
family runs one forward + one PEFT train step on CPU, asserting output
shapes and no NaNs. The FULL configs are exercised only by the dry-run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import OptimCfg
from repro.configs import ASSIGNED, get, get_smoke
from repro.core import peft
from repro.launch.specs import params_shapes
from repro.models import model as M
from repro.train.steps import build_train_step, make_state

KEY = jax.random.PRNGKey(0)
ARCHS = sorted(ASSIGNED)


def _batch(cfg, B=2, S=16):
    toks = jax.random.randint(KEY, (B, S), 10, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            KEY, (B, cfg.n_image_tokens, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            KEY, (B, cfg.n_audio_frames, cfg.d_model))
    if cfg.family == "encoder":
        batch["type_ids"] = jnp.zeros_like(toks)
        batch["labels"] = jnp.zeros((B,), jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(arch):
    cfg = peft.attach(get_smoke(arch), peft.strategy("hadamard"))
    p = M.init_params(KEY, cfg)
    b = _batch(cfg)
    if cfg.family == "encdec":
        logits, _ = M.forward_encdec(p, cfg, b["frames"], b["tokens"])
        want_len = b["tokens"].shape[1]
    elif cfg.family == "vlm":
        logits, _ = M.forward_lm(p, cfg, b["tokens"], patches=b["patches"])
        want_len = b["tokens"].shape[1] + cfg.n_image_tokens
    else:
        logits, _ = M.forward_lm(p, cfg, b["tokens"])
        want_len = b["tokens"].shape[1]
    assert logits.shape == (2, want_len, cfg.vocab_size)
    assert not jnp.isnan(logits).any()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = peft.attach(get_smoke(arch), peft.strategy("hadamard"))
    strat = peft.strategy("hadamard")
    ocfg = OptimCfg(lr=1e-3, total_steps=10)
    state = make_state(KEY, cfg, strat, ocfg)
    step = jax.jit(build_train_step(cfg, ocfg))
    state, metrics = step(state, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert int(state["step"]) == 1
    # adapter actually moved
    ad = state["trainable"]["blocks"]["g0"]["slot0"]["adapter"]["b"]
    assert float(jnp.abs(ad).max()) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_spec(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get(arch)
    spec = {
        "deepseek-moe-16b": dict(L=28, d=2048, H=16, kv=16, vocab=102400),
        "deepseek-v2-lite": dict(L=27, d=2048, H=16, kv=16, vocab=102400),
        "qwen3-moe-235b-a22b": dict(L=94, d=4096, H=64, kv=4, vocab=151936),
        "recurrentgemma-2b": dict(L=26, d=2560, H=10, kv=1, vocab=256000),
        "whisper-tiny": dict(L=8, d=384, H=6, kv=6, vocab=51865),
        "rwkv6-1.6b": dict(L=24, d=2048, H=32, kv=32, vocab=65536),
        "starcoder2-7b": dict(L=32, d=4608, H=36, kv=4, vocab=49152),
        "starcoder2-3b": dict(L=30, d=3072, H=24, kv=2, vocab=49152),
        "qwen3-0.6b": dict(L=28, d=1024, H=16, kv=8, vocab=151936),
        "gemma2-27b": dict(L=46, d=4608, H=32, kv=16, vocab=256000),
        "internvl2-76b": dict(L=80, d=8192, H=64, kv=8, vocab=128256),
    }[arch]
    assert cfg.n_layers == spec["L"]
    assert cfg.d_model == spec["d"]
    assert cfg.n_heads == spec["H"]
    assert cfg.n_kv_heads == spec["kv"]
    assert cfg.vocab_size == spec["vocab"]


@pytest.mark.parametrize("arch,n_b", [
    ("deepseek-moe-16b", 16.4e9), ("deepseek-v2-lite", 15.7e9),
    ("qwen3-moe-235b-a22b", 235e9),
    ("gemma2-27b", 27e9), ("internvl2-76b", 76e9),
    ("starcoder2-7b", 7e9), ("starcoder2-3b", 3e9),
    ("rwkv6-1.6b", 1.6e9), ("recurrentgemma-2b", 2.7e9),
    ("qwen3-0.6b", 0.6e9), ("whisper-tiny", 39e6),
])
def test_full_param_counts_in_range(arch, n_b):
    """Total param counts land near the advertised model sizes (counted on
    abstract shapes - nothing allocated)."""
    from repro.common import tree as tu

    shapes = params_shapes(get(arch))
    total = tu.count_params(shapes)
    assert 0.55 * n_b < total < 1.7 * n_b, f"{arch}: {total:.3g} vs {n_b:.3g}"


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "rwkv6-1.6b",
                                  "recurrentgemma-2b", "whisper-tiny",
                                  "deepseek-v2-lite"])
def test_smoke_decode_step(arch):
    cfg = peft.attach(get_smoke(arch), peft.strategy("hadamard"))
    p = M.init_params(KEY, cfg)
    B, S = 2, 8
    if cfg.family == "encdec":
        frames = jax.random.normal(KEY, (B, cfg.n_audio_frames, cfg.d_model))
        toks = jax.random.randint(KEY, (B, S), 10, cfg.vocab_size)
        _, caches = M.prefill_encdec(p, cfg, frames, toks, cache_len=S + 4)
        logits, caches = M.decode_encdec(p, cfg, caches,
                                         toks[:, -1:], jnp.int32(S))
    else:
        toks = jax.random.randint(KEY, (B, S), 10, cfg.vocab_size)
        _, caches = M.prefill_lm(p, cfg, toks, cache_len=S + 4)
        logits, caches = M.decode_lm(p, cfg, caches, toks[:, -1:], jnp.int32(S))
    assert logits.shape[-1] == cfg.vocab_size
    assert not jnp.isnan(logits).any()


def test_long_context_skip_flags():
    """long_500k applicability matches DESIGN.md §5."""
    from repro.configs import get

    assert get("rwkv6-1.6b").sub_quadratic
    assert get("recurrentgemma-2b").sub_quadratic
    for a in ["gemma2-27b", "starcoder2-7b", "qwen3-moe-235b-a22b",
              "whisper-tiny", "internvl2-76b"]:
        assert not get(a).sub_quadratic, a
