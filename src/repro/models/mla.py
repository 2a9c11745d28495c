"""Multi-head latent attention (DeepSeek-V2), with an uncompressed query.

Per layer, with the slot's `attn` weights:
  q    = x @ wq                        (B, S, H, nope + rope)
  kv_a = x @ wkv_a                     (B, S, kv_rank + rope)
  c    = RMSNorm(kv_a[:kv_rank]) * kv_norm          the latent
  k_pe = rope(kv_a[kv_rank:])          one rope key shared by every head
  k_h  = [c @ W_UK_h, k_pe],  v_h = c @ W_UV_h      (wkv_b = [W_UK_h | W_UV_h])
  y    = concat_h softmax(q_h . k_h * scale) v_h  @ wo
The rope dims are de-interleaved (even, then odd) before rotate-half, as
in the published modelling code; the rope is YaRN where
`cfg.rope_scaling` is set, and the softmax scale is (nope + rope)^-0.5
times YaRN's mscale(mscale_all_dim)^2.

The same attention in two forms:
  * expanded (train, prefill): per-head K (nope + rope wide) and V
    (v_dim wide) from the latent of the call's own tokens. Every key is
    computed in the call anyway, and the heads' 192/128 widths cost a
    third of the absorbed form's 576/512 per score.
  * absorbed (decode, paged extend): W_UK folds into the query
    (q_lat_h = q_nope_h @ W_UK_h^T) and W_UV into the output, so the
    attention reads the latent cache itself as one KV head, kv_rank +
    rope wide for scores and its first kv_rank entries for values. No
    per-head K or V is ever built from the cache.

The cache holds c ‖ roped k_pe, kv_rank + rope values per token per
layer, as the leaf `latent`: (B, S, ·) out of a prefill, (repeats, B,
S, ·) as a slot cache, (repeats, num_blocks, page, ·) as a paged pool
leaf, the view gaining its one KV head when read; GQA's cache helpers
(`attention.write_read_cache`, `prefill_cache`) write and read it. The
leaf is `MLACfg.cache_dim` wide: the kv_rank + rope values, then zeros
up to a multiple of 128 (576 -> 640), which the query matches with
zeros. The
TPU stores a 576-wide minor dim padded to 640 anyway, and at 576 XLA
lays the pool out transposed for the decode step's gather and scatter
and copies all of it in and out of every step. Full attention only;
the cache is never quantized.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.types import ModelCfg, Slot
from repro.models import flash
from repro.models.attention import prefill_cache, write_read_cache
from repro.models.layers import (apply_rope, dense_init, rms_head_norm,
                                 yarn_freqs, yarn_mscale)
from repro.obs.profile import scope
from repro.quant.qtensor import is_qtensor, qdense


def mla_init(key, cfg: ModelCfg):
    m, H, d = cfg.mla, cfg.n_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, H * (m.nope_dim + m.rope_dim), cfg.pdtype),
        "wkv_a": dense_init(ks[1], d, m.latent_dim, cfg.pdtype),
        "kv_norm": jnp.ones((m.kv_rank,), cfg.pdtype),
        "wkv_b": dense_init(ks[2], m.kv_rank, H * (m.nope_dim + m.v_dim),
                            cfg.pdtype),
        "wo": dense_init(ks[3], H * m.v_dim, d, cfg.pdtype),
    }


def softmax_scale(cfg: ModelCfg) -> float:
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.nope_dim + m.rope_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def rope(cfg: ModelCfg, x, positions):
    """The published rope of the `rope_dim` part: de-interleave, then
    rotate-half (YaRN frequencies and cos/sin scale when configured)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    y = cfg.rope_scaling
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta,
                      freqs=yarn_freqs(x.shape[-1], cfg.rope_theta, y),
                      mscale=yarn_mscale(y.factor, y.mscale)
                      / yarn_mscale(y.factor, y.mscale_all_dim))


def _attend(cfg: ModelCfg, q, k, v, **kw):
    with scope("repro.attn_core"):
        return flash.attend(q, k, v, scale=softmax_scale(cfg), cap=0.0,
                            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                            tile_dtype=cfg.attn_tile_dtype, **kw)


def _expanded(p, cfg: ModelCfg, q, c, k_pe, q_pos, causal):
    """Attention of the call's queries over its own tokens, per-head K/V."""
    m, H = cfg.mla, cfg.n_heads
    B, S = c.shape[:2]
    kv = qdense(c, p["wkv_b"], cfg.cdtype, tag="attn/wkv_b")
    kv = kv.reshape(B, S, H, m.nope_dim + m.v_dim)
    k = jnp.concatenate(
        [kv[..., :m.nope_dim],
         jnp.broadcast_to(k_pe[:, :, None], (B, S, H, m.rope_dim))], axis=-1)
    out = _attend(cfg, q[:, :, :, None], k, kv[..., m.nope_dim:],
                  q_pos=q_pos, kv_pos=q_pos, kv_len=jnp.int32(S),
                  causal=causal)
    return out.reshape(B, S, H * m.v_dim)


def apply_mla(p, cfg: ModelCfg, slot: Slot, x, *, q_pos, causal=True,
              cache=None, cache_len=None, write_pos=None, block_tables=None,
              paged_kv_len=None, layer=None):
    """The attention block's output before the adapter, and the new cache
    (same cache protocol as `attention.apply_attn`)."""
    if slot.window is not None:
        raise ValueError("latent attention is full attention only "
                         f"(got window={slot.window})")
    m, H, cdt = cfg.mla, cfg.n_heads, cfg.cdtype
    B, S, _ = x.shape
    q = qdense(x, p["wq"], cdt, tag="attn/wq").reshape(
        B, S, H, m.nope_dim + m.rope_dim)
    kv_a = qdense(x, p["wkv_a"], cdt, tag="attn/wkv_a")
    c = rms_head_norm(p["kv_norm"], kv_a[..., :m.kv_rank], cfg.norm_eps)
    k_pe = rope(cfg, kv_a[..., None, m.kv_rank:], q_pos)[:, :, 0]
    q_nope, q_pe = q[..., :m.nope_dim], rope(cfg, q[..., m.nope_dim:], q_pos)
    pad = jnp.zeros((B, S, m.cache_dim - m.latent_dim), cdt)
    lat = jnp.concatenate([c, k_pe, pad], axis=-1)  # (B, S, cache_dim)

    if cache is None:  # train, or prefill building the cache
        out = _expanded(p, cfg, jnp.concatenate([q_nope, q_pe], -1), c, k_pe,
                        q_pos, causal)
        new_cache = (None if cache_len is None
                     else prefill_cache({"latent": lat}, cache_len))
        return qdense(out, p["wo"], cdt, tag="attn/wo"), new_cache

    with scope("repro.mla_absorb"):
        wkv_b = p["wkv_b"].astype(cdt).reshape(m.kv_rank, H,
                                               m.nope_dim + m.v_dim)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wkv_b[..., :m.nope_dim])
        q_full = jnp.concatenate(  # (B, S, 1, H, cache_dim)
            [q_lat, q_pe, jnp.broadcast_to(pad[:, :, None], (B, S, H) + pad.shape[2:])],
            -1)[:, :, None]
    if is_qtensor(cache["latent"]):
        raise ValueError("latent attention keeps its cache in the compute "
                         "dtype; int8/fp8 KV is not supported")
    new_cache, views, kv_pos, kv_len = write_read_cache(
        cache, {"latent": lat}, layer, write_pos, block_tables=block_tables,
        paged_kv_len=paged_kv_len, dtype=cdt)
    view = views["latent"][:, :, None]  # (B, L, 1, cache_dim): one KV head
    # the view is the values too: one array, so its pad and chunk slices
    # are made once for scores and values; the columns past kv_rank of
    # the output are dropped
    o_lat = _attend(cfg, q_full, view, view, q_pos=q_pos, kv_pos=kv_pos,
                    kv_len=kv_len, causal=causal)[..., :m.kv_rank]
    with scope("repro.mla_absorb"):
        out = jnp.einsum("bshr,rhv->bshv", o_lat.reshape(B, S, H, m.kv_rank),
                         wkv_b[..., m.nope_dim:]).reshape(B, S, H * m.v_dim)
    return qdense(out, p["wo"], cdt, tag="attn/wo"), new_cache
