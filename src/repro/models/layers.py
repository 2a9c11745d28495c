"""Shared neural-net building blocks (pure functional init/apply pairs)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.types import ModelCfg, YarnCfg
from repro.obs.profile import scope
from repro.quant.qtensor import qdense

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = 0.02 if scale is None else scale
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out)) * scale).astype(
        dtype
    )


def embed_init(key, n: int, d: int, dtype, scale: float = 0.02):
    return (jax.random.truncated_normal(key, -2.0, 2.0, (n, d)) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------


def norm_init(cfg: ModelCfg, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), cfg.pdtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), cfg.pdtype)
    return p


def apply_norm(p, cfg: ModelCfg, x):
    """RMSNorm or LayerNorm, computed in fp32 for stability."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + cfg.norm_eps)
        # gemma-style (1 + scale) parameterisation is not used; plain scale.
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(dtype)


def rms_head_norm(scale, x, eps=1e-6):
    """Per-head RMSNorm over the trailing head_dim (qwen3 qk-norm)."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / MLP
# ---------------------------------------------------------------------------


def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


def mlp_init(key, cfg: ModelCfg, d_in: Optional[int] = None, d_ff: Optional[int] = None):
    d_in = d_in or cfg.d_model
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "wi": dense_init(k1, d_in, d_ff, cfg.pdtype),
        "wo": dense_init(k2, d_ff, d_in, cfg.pdtype),
    }
    if cfg.gated_mlp:
        p["wg"] = dense_init(k3, d_in, d_ff, cfg.pdtype)
    if cfg.mlp_bias:
        p["bi"] = jnp.zeros((d_ff,), cfg.pdtype)
        p["bo"] = jnp.zeros((d_in,), cfg.pdtype)
    return p


@scope("repro.mlp")
def apply_mlp(p, cfg: ModelCfg, x, ia3=None):
    h = qdense(x, p["wi"], cfg.cdtype, tag="mlp/wi")
    if "bi" in p:
        h = h + p["bi"].astype(cfg.cdtype)
    if cfg.gated_mlp:
        h = act_fn(cfg.act)(h) * qdense(x, p["wg"], cfg.cdtype, tag="mlp/wg")
    else:
        h = act_fn(cfg.act)(h)
    if ia3 is not None:  # IA3 baseline: learned scale on the ffn activation
        h = h * ia3.astype(cfg.cdtype)
    y = qdense(h, p["wo"], cfg.cdtype, tag="mlp/wo")
    if "bo" in p:
        y = y + p["bo"].astype(cfg.cdtype)
    return y


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)  # (head_dim/2,)


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """DeepSeek-V2's `yarn_get_mscale`: 0.1 * mscale * ln(scale) + 1."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(head_dim: int, theta: float, y: YarnCfg):
    """YaRN inverse frequencies (DeepSeek-V2's DeepseekV2YarnRotaryEmbedding):
    dims that turn fewer than beta_slow times within the original context
    are interpolated by `factor`, those that turn more than beta_fast
    times keep their frequency, with a linear ramp between."""
    def dim_of(rotations):  # yarn_find_correction_dim
        return (head_dim * math.log(y.original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(y.beta_fast)), 0)
    high = min(math.ceil(dim_of(y.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_freqs(head_dim, theta)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x, positions, theta: float, freqs=None, mscale: float = 1.0):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    freqs overrides the plain inverse frequencies (YaRN), and mscale
    scales cos and sin (YaRN's mscale / mscale_all_dim)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta) if freqs is None else freqs
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Softcap (gemma2)
# ---------------------------------------------------------------------------


def softcap(x, cap: float):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap
