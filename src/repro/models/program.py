"""Layer-program machinery: heterogeneous block patterns scanned with
stacked parameters.

A model's depth is a tuple of `Group`s; each group is `repeats` copies of a
slot pattern (e.g. recurrentgemma: (rec, rec, attn) x 8 + (rec, rec) x 1).
Parameters for a group are stacked on a leading `repeats` dim and the group
is executed with `lax.scan`, which keeps the HLO size O(pattern) instead of
O(depth) - essential for compiling 94-layer configs in the dry-run - and is
also the idiomatic TPU training structure (remat wraps the scan body).

The paper's Hadamard adapter lives inside each block's params under
'adapter' (stacked (repeats, d) in a group), so PEFT masks address it with
one regex across every architecture.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.costmode import scan_unroll
from repro.common.types import AdapterCfg, Group, ModelCfg, Slot
from repro.dist.api import constrain
from repro.models.attention import apply_attn, apply_hadamard, attn_init
from repro.models.layers import apply_mlp, apply_norm, dense_init, mlp_init, norm_init
from repro.models.moe import moe_apply, moe_init
from repro.models.recurrent import rec_apply, rec_cache_init, rec_init
from repro.models.rwkv import (
    rwkv_cache_init,
    rwkv_channel_mix,
    rwkv_cm_init,
    rwkv_time_mix,
    rwkv_tm_init,
)

# ---------------------------------------------------------------------------
# Adapter params
# ---------------------------------------------------------------------------


def adapter_init(key, cfg: ModelCfg, slot: Slot):
    a = cfg.adapter
    if not a.enabled:
        return None
    if a.kind == "hadamard":
        dim = cfg.q_dim if a.position == "attn_concat" and slot.kind == "attn" else cfg.d_model
        # w=1, b=0: the identity - "equivalent to not adding any adapter" (paper 3.1)
        return {"w": jnp.ones((dim,), jnp.float32), "b": jnp.zeros((dim,), jnp.float32)}
    if a.kind == "lora":
        r = a.lora_rank
        ks = jax.random.split(key, 2)
        return {
            "qa": dense_init(ks[0], cfg.d_model, r, jnp.float32),
            "qb": jnp.zeros((r, cfg.q_dim), jnp.float32),
            "va": dense_init(ks[1], cfg.d_model, r, jnp.float32),
            "vb": jnp.zeros((r, cfg.kv_dim), jnp.float32),
        }
    if a.kind == "ia3":
        return {
            "lk": jnp.ones((cfg.kv_dim,), jnp.float32),
            "lv": jnp.ones((cfg.kv_dim,), jnp.float32),
            "lff": jnp.ones((cfg.d_ff,), jnp.float32),
        }
    if a.kind == "houlsby":
        h = a.houlsby_dim
        ks = jax.random.split(key, 2)
        out = {}
        for name, k in zip(("attn_ad", "ffn_ad"), ks):
            out[name] = {
                "down": dense_init(k, cfg.d_model, h, jnp.float32),
                "down_b": jnp.zeros((h,), jnp.float32),
                "up": jnp.zeros((h, cfg.d_model), jnp.float32),
                "up_b": jnp.zeros((cfg.d_model,), jnp.float32),
            }
        return out
    raise ValueError(f"unknown adapter kind {a.kind}")


def _houlsby(ad, x):
    h = jax.nn.gelu(x @ ad["down"].astype(x.dtype) + ad["down_b"].astype(x.dtype))
    return x + h @ ad["up"].astype(x.dtype) + ad["up_b"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------


def block_init(key, cfg: ModelCfg, slot: Slot):
    ks = jax.random.split(key, 8)
    p = {"attn_norm": norm_init(cfg), "ffn_norm": norm_init(cfg)}
    if slot.kind == "attn":
        p["attn"] = attn_init(ks[0], cfg)
    elif slot.kind == "rec":
        p["rec"] = rec_init(ks[0], cfg)
    elif slot.kind == "rwkv":
        p["rwkv_tm"] = rwkv_tm_init(ks[0], cfg)
    else:
        raise ValueError(f"unknown slot kind {slot.kind}")

    if slot.cross_attn:
        p["cross_norm"] = norm_init(cfg)
        p["cross"] = attn_init(ks[1], cfg, cross=True)

    if slot.kind == "rwkv":
        p["rwkv_cm"] = rwkv_cm_init(ks[2], cfg)
    elif slot.moe:
        p["moe"] = moe_init(ks[2], cfg)
    else:
        p["mlp"] = mlp_init(ks[2], cfg)

    if cfg.post_norms:
        p["post_attn_norm"] = norm_init(cfg)
        p["post_ffn_norm"] = norm_init(cfg)

    ad = adapter_init(ks[3], cfg, slot)
    if ad is not None:
        p["adapter"] = ad
    return p


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------


def _layer_of(stacked, layer):
    """Layer `layer` of a stacked state tree (decode), else the tree."""
    if layer is None or stacked is None:
        return stacked
    return jax.tree.map(lambda a: a[layer], stacked)


def _set_layer(stacked, layer, new):
    """Write one layer's new state into the stacked tree (decode, in
    place on the scan's carry), else return the new state."""
    if layer is None:
        return new
    return jax.tree.map(lambda s, n: s.at[layer].set(n), stacked, new)


def block_apply(p, cfg: ModelCfg, slot: Slot, x, *, q_pos, causal,
                cache=None, cache_len=None, write_pos=None, enc_out=None,
                block_tables=None, paged_kv_len=None, layer=None):
    """Returns (x, new_cache, aux_loss).

    layer (decode): this block's traced index in its group; `cache` then
    holds the group's stacked caches, read and written at that index.
    """
    acfg: AdapterCfg = cfg.adapter
    ad = p.get("adapter")
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    c = cache or {}

    if cfg.ln_placement == "post":
        # BERT-style: sublayer -> residual add -> LayerNorm
        a, nc = apply_attn(p["attn"], cfg, slot, x, q_pos=q_pos, causal=causal,
                           cache=c.get("attn"), cache_len=cache_len,
                           write_pos=write_pos, adapter=ad, layer=layer)
        if ad is not None and acfg.kind == "houlsby":
            a = _houlsby(ad["attn_ad"], a)
        if nc is not None:
            new_cache["attn"] = nc
        x = apply_norm(p["attn_norm"], cfg, x + a)  # "A": attention-output norm
        f = apply_mlp(p["mlp"], cfg, x,
                      ia3=ad.get("lff") if (ad and acfg.kind == "ia3") else None)
        if ad is not None and acfg.kind == "houlsby":
            f = _houlsby(ad["ffn_ad"], f)
        x = apply_norm(p["ffn_norm"], cfg, x + f)  # "N": post-intermediate norm
        return x, (new_cache or None), aux

    # --- pre-LN path (all modern archs) ---
    h = apply_norm(p["attn_norm"], cfg, x)
    if slot.kind == "attn":
        a, nc = apply_attn(p["attn"], cfg, slot, h, q_pos=q_pos, causal=causal,
                           cache=c.get("attn"), cache_len=cache_len,
                           write_pos=write_pos, adapter=ad,
                           block_tables=block_tables,
                           paged_kv_len=paged_kv_len, layer=layer)
        if nc is not None:
            new_cache["attn"] = nc
    elif slot.kind == "rec":
        a, nc = rec_apply(p["rec"], cfg, h, _layer_of(c.get("rec"), layer))
        if cache_len is not None or cache:
            new_cache["rec"] = _set_layer(c.get("rec"), layer, nc)
        if ad is not None and acfg.kind == "hadamard":
            a = apply_hadamard(a, ad)  # generalized: affine on mixer output
    else:  # rwkv
        a, nc_tm = rwkv_time_mix(p["rwkv_tm"], cfg, h,
                                 _layer_of(c.get("rwkv"), layer))
        if ad is not None and acfg.kind == "hadamard":
            a = apply_hadamard(a, ad)
    if ad is not None and acfg.kind == "houlsby":
        a = _houlsby(ad["attn_ad"], a)
    if cfg.post_norms:
        a = apply_norm(p["post_attn_norm"], cfg, a)
    x = x + a

    if slot.cross_attn:
        hc = apply_norm(p["cross_norm"], cfg, x)
        ca, ncc = apply_attn(p["cross"], cfg, slot, hc, q_pos=q_pos, causal=False,
                             kv_x=enc_out, cache=c.get("cross"),
                             cache_len=cache_len, adapter=None, layer=layer)
        if ncc is not None:
            new_cache["cross"] = ncc
        x = x + ca

    h = apply_norm(p["ffn_norm"], cfg, x)
    if slot.kind == "rwkv":
        f, nc_cm = rwkv_channel_mix(p["rwkv_cm"], cfg, h,
                                    _layer_of(c.get("rwkv"), layer))
        if cache_len is not None or c.get("rwkv") is not None:
            new_cache["rwkv"] = _set_layer(c.get("rwkv"), layer,
                                           {**nc_tm, **nc_cm})
    elif slot.moe:
        f, aux = moe_apply(p["moe"], cfg, h)
    else:
        f = apply_mlp(p["mlp"], cfg, h,
                      ia3=ad.get("lff") if (ad and acfg.kind == "ia3") else None)
    if ad is not None and acfg.kind == "houlsby":
        f = _houlsby(ad["ffn_ad"], f)
    if cfg.post_norms:
        f = apply_norm(p["post_ffn_norm"], cfg, f)
    x = x + f
    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Group (scan) init / cache / apply
# ---------------------------------------------------------------------------


def group_init(key, cfg: ModelCfg, group: Group):
    def init_one(k):
        sks = jax.random.split(k, len(group.slots))
        return {f"slot{i}": block_init(sk, cfg, s)
                for i, (sk, s) in enumerate(zip(sks, group.slots))}

    keys = jax.random.split(key, group.repeats)
    return jax.vmap(init_one)(keys)


def group_cache_init(cfg: ModelCfg, group: Group, batch: int, cache_len: int,
                     enc_len: Optional[int] = None):
    """Zeroed stacked cache (used to build decode input specs)."""
    def one_slot(slot: Slot):
        c = {}
        if slot.kind == "attn" and cfg.mla is not None:
            c["attn"] = {"latent": jnp.zeros(
                (batch, cache_len, cfg.mla.cache_dim), cfg.cdtype)}
        elif slot.kind == "attn":
            size = cache_len if slot.window is None else min(slot.window, cache_len)
            c["attn"] = {
                "k": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), cfg.cdtype),
                "v": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), cfg.cdtype),
            }
        elif slot.kind == "rec":
            c["rec"] = rec_cache_init(cfg, batch, cfg.cdtype)
        else:
            c["rwkv"] = rwkv_cache_init(cfg, batch, cfg.cdtype)
        if slot.cross_attn:
            el = enc_len or cfg.n_audio_frames
            c["cross"] = {
                "ck": jnp.zeros((batch, el, cfg.n_kv_heads, cfg.head_dim), cfg.cdtype),
                "cv": jnp.zeros((batch, el, cfg.n_kv_heads, cfg.head_dim), cfg.cdtype),
            }
        return c

    per_layer = {f"slot{i}": one_slot(s) for i, s in enumerate(group.slots)}
    return jax.tree.map(
        lambda z: jnp.broadcast_to(z, (group.repeats,) + z.shape), per_layer
    )


def group_pool_init(cfg: ModelCfg, group: Group, num_blocks: int, page: int,
                    quant: Optional[str] = None):
    """Zeroed stacked paged block pool for one group.

    Every attention slot gets K/V pools of shape
    (repeats, num_blocks, page, KH, Dh); with `quant` ('int8'/'fp8') the
    pool leaves are QTensors with per-token-per-head scales
    (repeats, num_blocks, page, KH, 1) - the layout the paged decode path
    writes with `quantize(k, axis=-1)`. A latent-attention slot
    (`cfg.mla`) gets one leaf `latent` of shape
    (repeats, num_blocks, page, mla.cache_dim) instead (kv_rank +
    rope_dim values, zero-padded to 128 lanes), never quantized. Block 0
    is the allocator's reserved null block (unmapped table entries point
    there and its rows are masked, never read). Paged serving is
    attention-only: recurrent / rwkv / cross-attention slots have no
    block-structured state.
    """
    from repro.quant.qtensor import QTensor, _storage_dtype

    for slot in group.slots:
        if slot.kind != "attn" or slot.cross_attn:
            raise ValueError(
                "paged KV pools require pure attention slots (got "
                f"kind={slot.kind!r}, cross_attn={slot.cross_attn})")
    if cfg.mla is not None:
        if quant:
            raise ValueError(
                f"kv_quant={quant!r} is not supported for latent attention: "
                "its pool holds the latent in the compute dtype")
        lat = (group.repeats, num_blocks, page, cfg.mla.cache_dim)
        return {f"slot{i}": {"attn": {"latent": jnp.zeros(lat, cfg.cdtype)}}
                for i in range(len(group.slots))}

    def one_slot():
        kv = (group.repeats, num_blocks, page, cfg.n_kv_heads, cfg.head_dim)

        # distinct buffers per leaf: the pool is donated through every
        # decode tick, and XLA rejects donating one buffer twice
        def qt():
            return QTensor(jnp.zeros(kv, _storage_dtype(quant)),
                           jnp.ones(kv[:-1] + (1,), jnp.float32))

        if quant:
            return {"k": qt(), "v": qt()}
        return {"k": jnp.zeros(kv, cfg.cdtype), "v": jnp.zeros(kv, cfg.cdtype)}

    return {f"slot{i}": {"attn": one_slot()} for i in range(len(group.slots))}


def _remat_policy(cfg: ModelCfg):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def group_apply(pg, cfg: ModelCfg, group: Group, x, *, q_pos, causal,
                mode: str = "train", caches=None, cache_len=None,
                write_pos=None, enc_out=None, block_tables=None,
                paged_kv_len=None):
    """Run `repeats` iterations of the slot pattern.

    mode: 'train' (no cache), 'prefill' (emit caches), 'decode' (consume +
    update caches; S=1, or S>1 for a paged extend).
    Decode carries the group's stacked caches (paged block pools, slot
    caches, recurrent state) through the scan as part of the carry, with
    the layer index as a scanned input: each layer scatters its new K/V
    (or state) into the stacked arrays at [layer, ...] and gathers its
    view from them there. Scanned as xs/ys they would be rebuilt into a
    second stacked buffer every call, which donation cannot alias; carried,
    they are updated in place and the step's output aliases its donated
    input.
    block_tables (paged decode): one (B, nbt) table shared by every layer,
    closed over by the scan body (the logical->physical mapping is
    sequence-level state).
    Returns (x, new_caches, aux_sum).
    """
    decode = mode == "decode"

    def body(carry, xs):
        x, aux, stacked = carry  # stacked: the decode caches, else None
        p_layer, layer = xs if decode else (xs, None)
        new_caches = {}
        for i, slot in enumerate(group.slots):
            x, nc, a = block_apply(
                p_layer[f"slot{i}"], cfg, slot, x,
                q_pos=q_pos, causal=causal,
                cache=(stacked or {}).get(f"slot{i}"),
                cache_len=cache_len if mode == "prefill" else None,
                write_pos=write_pos, enc_out=enc_out,
                block_tables=block_tables, paged_kv_len=paged_kv_len,
                layer=layer,
            )
            aux = aux + a
            if nc is not None:
                new_caches[f"slot{i}"] = nc
        if cfg.sequence_sharding and not decode and x.shape[1] > 1:
            x = constrain(x, "dp", "model", None)
        out = new_caches or None
        return ((x, aux, out), None) if decode else ((x, aux, None), out)

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body, policy=_remat_policy(cfg),
                              prevent_cse=False)

    xs = (pg, jnp.arange(group.repeats, dtype=jnp.int32)) if decode else pg
    (x, aux, carried), emitted = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), caches if decode else None),
        xs, unroll=scan_unroll(group.repeats),
    )
    return x, (carried if decode else emitted), aux
    (x, aux), new_caches = jax.lax.scan(body, (x, aux0), pg, unroll=unroll)
    return x, new_caches, aux
