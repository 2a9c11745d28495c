"""Multi-head attention block with GQA, RoPE, qk-norm, local windows,
soft-capping, KV caches (linear + ring-buffer) and adapter hooks.

The adapter hooks are how the paper's technique (and the LoRA / IA3
baselines) reach inside attention without forking the model code.

Cache protocol (per attention slot):
  train:   cache=None, cache_len=None           -> returns (y, None)
  prefill: cache=None, cache_len=S_cache        -> returns (y, fresh cache)
  decode:  cache=dict, write_pos, layer         -> returns (y, updated cache)
At decode the cache is the layer group's stacked cache (leading `repeats`
dim, carried through the layer scan): this layer writes at [layer, ...]
and reads its own rows from there, so the stack is updated in place.
Cross-attention slots store the encoder K/V at prefill ('ck'/'cv') and read
them back at decode. A latent-attention config (`cfg.mla`) runs its own
block (models/mla.py), whose cache is one `latent` leaf under the same
protocol and the same helpers (`write_read_cache`, `prefill_cache`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.types import AdapterCfg, ModelCfg, Slot
from repro.models import flash
from repro.models.layers import apply_rope, dense_init, rms_head_norm
from repro.obs.profile import scope
from repro.quant.qtensor import QTensor, is_qtensor, qdense, quantize

INVALID_POS = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def attn_init(key, cfg: ModelCfg, cross: bool = False):
    if cfg.mla is not None and not cross:
        from repro.models.mla import mla_init  # deferred: mla uses the cache helpers below
        return mla_init(key, cfg)
    ks = jax.random.split(key, 6)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": dense_init(ks[0], d, qd, cfg.pdtype),
        "wk": dense_init(ks[1], d, kvd, cfg.pdtype),
        "wv": dense_init(ks[2], d, kvd, cfg.pdtype),
        "wo": dense_init(ks[3], qd, d, cfg.pdtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((qd,), cfg.pdtype)
        p["bk"] = jnp.zeros((kvd,), cfg.pdtype)
        p["bv"] = jnp.zeros((kvd,), cfg.pdtype)
        p["bo"] = jnp.zeros((d,), cfg.pdtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((cfg.head_dim,), cfg.pdtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), cfg.pdtype)
    return p


def attn_cache_shape(cfg: ModelCfg, slot: Slot, batch: int, cache_len: int):
    size = cache_len if slot.window is None else min(slot.window, cache_len)
    kv = (batch, size, cfg.n_kv_heads, cfg.head_dim)
    return {"k": kv, "v": kv}


def ring_positions(size: int, pos):
    """Absolute positions held by each ring-buffer slot when the current
    write position is `pos` (slot i holds the latest p <= pos with
    p % size == i). Slots never written map to INVALID_POS.

    pos may be a scalar -> (size,), or a (B,) vector of per-row write
    positions (continuous batching) -> (B, size)."""
    i = jnp.arange(size)
    p = jnp.asarray(pos)[..., None]
    p = p - ((p - i) % size)
    out = jnp.where(p < 0, INVALID_POS, p)
    return out if jnp.asarray(pos).ndim else out.reshape(size)


# ---------------------------------------------------------------------------
# Adapter hooks
# ---------------------------------------------------------------------------


def _lora_delta(x, a, b, alpha: float, rank: int):
    return (x @ a.astype(x.dtype)) @ b.astype(x.dtype) * (alpha / rank)


@scope("repro.hadamard_adapter")
def apply_hadamard(y, ad):
    """The paper's Eq. 5: elementwise affine on the feature dim.

    Supports per-request adapters for multi-task serving: when w/b are
    (B, d) they broadcast over the sequence dim of y (B, S, d).
    """
    w = ad["w"].astype(y.dtype)
    b = ad["b"].astype(y.dtype)
    if w.ndim == 2:  # (B, d): one adapter per request in the batch
        w, b = w[:, None], b[:, None]
    return y * w + b


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def apply_attn(
    p,
    cfg: ModelCfg,
    slot: Slot,
    x,
    *,
    q_pos,
    causal: bool = True,
    kv_x=None,  # cross-attention source (B, S_enc, d)
    cache=None,  # decode (or cross-decode) cache for this slot
    cache_len: Optional[int] = None,  # prefill: build a cache of this size
    write_pos=None,  # decode: scalar / (B,) / (B, S) absolute write positions
    adapter=None,
    adapter_cfg: Optional[AdapterCfg] = None,
    block_tables=None,  # paged decode/extend: (B, nbt) physical block ids
    paged_kv_len=None,  # paged extend: traced valid-length override
    layer=None,  # decode: this layer's index into the stacked cache
):
    B, S, _ = x.shape
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    acfg = adapter_cfg or cfg.adapter
    cdt = cfg.cdtype
    if cfg.mla is not None:
        if kv_x is not None or (adapter is not None and (
                acfg.kind in ("lora", "ia3") or acfg.position != "attn_out")):
            raise ValueError("latent attention has no cross-attention and "
                             "takes only the Hadamard adapter on its output")
        from repro.models.mla import apply_mla
        y, new_cache = apply_mla(
            p, cfg, slot, x, q_pos=q_pos, causal=causal, cache=cache,
            cache_len=cache_len, write_pos=write_pos,
            block_tables=block_tables, paged_kv_len=paged_kv_len,
            layer=layer)
        if adapter is not None and acfg.kind == "hadamard":
            y = apply_hadamard(y, adapter)
        return y, new_cache
    is_cross = kv_x is not None or (cache is not None and "ck" in cache)

    q = qdense(x, p["wq"], cdt, tag="attn/wq")
    if adapter is not None and acfg.kind == "lora":
        q = q + _lora_delta(x, adapter["qa"], adapter["qb"], acfg.lora_alpha,
                            acfg.lora_rank)
    if "bq" in p:
        q = q + p["bq"].astype(cdt)
    q = q.reshape(B, S, H, Dh)
    if cfg.qk_norm and "q_norm" in p:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
    if cfg.pos == "rope" and not is_cross:
        q = apply_rope(q, q_pos, cfg.rope_theta)

    k = v = None
    if not (is_cross and cache is not None):  # cross-decode skips k/v compute
        src = x if kv_x is None else kv_x
        k = qdense(src, p["wk"], cdt, tag="attn/wk")
        v = qdense(src, p["wv"], cdt, tag="attn/wv")
        if adapter is not None and acfg.kind == "lora":
            v = v + _lora_delta(src, adapter["va"], adapter["vb"],
                                acfg.lora_alpha, acfg.lora_rank)
        if "bk" in p:
            k = k + p["bk"].astype(cdt)
            v = v + p["bv"].astype(cdt)
        k = k.reshape(B, -1, KH, Dh)
        v = v.reshape(B, -1, KH, Dh)
        if cfg.qk_norm and "k_norm" in p and not is_cross:
            k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
        if cfg.pos == "rope" and not is_cross:
            if write_pos is None:
                kpos = q_pos
            else:
                wp = jnp.asarray(write_pos, jnp.int32)
                # scalar: all rows write position wp; (B,): per-row
                # positions; (B, S): per-row-per-token (paged extend)
                if wp.ndim == 2:
                    kpos = wp
                elif wp.ndim == 1:
                    kpos = wp[:, None]
                else:
                    kpos = jnp.full((S,), wp, jnp.int32)
            k = apply_rope(k, kpos, cfg.rope_theta)
        if adapter is not None and acfg.kind == "ia3":
            k = k * adapter["lk"].astype(cdt).reshape(KH, Dh)
            v = v * adapter["lv"].astype(cdt).reshape(KH, Dh)
        if cfg.replicate_kv and S > 1:
            # Perf lever: materialize K/V once per layer, replicated over the
            # model axis. Without this, sequence-sharded residuals make XLA
            # re-gather K/V inside EVERY flash kv-chunk iteration (measured
            # ~8 GB/layer/device of collectives on qwen3-0.6b train_4k).
            from repro.dist.api import constrain as _con

            k = _con(k, "dp", None, None, None)
            v = _con(v, "dp", None, None, None)

    # ----- cache handling -----
    new_cache = None
    if is_cross:
        if cache is not None:  # decode: read stored encoder K/V
            k_att, v_att = cache["ck"][layer], cache["cv"][layer]
            new_cache = cache
        else:
            k_att, v_att = k, v
            if cache_len is not None:
                new_cache = {"ck": k, "cv": v}
        kv_pos = jnp.arange(k_att.shape[1])
        eff_len = k_att.shape[1]
    elif cache is not None and write_pos is not None:  # self-attn decode
        new_cache, views, kv_pos, eff_len = write_read_cache(
            cache, {"k": k, "v": v}, layer, write_pos, window=slot.window,
            block_tables=block_tables, paged_kv_len=paged_kv_len, dtype=cdt)
        k_att, v_att = views["k"], views["v"]
    elif cache_len is not None:  # self-attn prefill: build the cache
        kv_pos = q_pos
        eff_len = S
        k_att, v_att = k, v
        new_cache = prefill_cache({"k": k, "v": v}, cache_len, slot.window)
    else:  # train
        kv_pos = q_pos
        eff_len = S
        k_att, v_att = k, v

    G = H // KH
    qg = q.reshape(B, S, KH, G, Dh)
    scale = cfg.query_scale if cfg.query_scale is not None else Dh**-0.5
    with scope("repro.attn_core"):
        out = flash.attend(
            qg, k_att, v_att,
            q_pos=q_pos, kv_pos=kv_pos, kv_len=eff_len,
            causal=causal and not is_cross,
            window=slot.window, scale=scale, cap=cfg.attn_softcap,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            tile_dtype=cfg.attn_tile_dtype,
        )
    out = out.reshape(B, S, H * Dh)

    # --- paper Eq. 7 literal placement: adapter on Concat(heads) ---
    if adapter is not None and acfg.kind == "hadamard" and acfg.position == "attn_concat":
        out = apply_hadamard(out, adapter)

    y = qdense(out, p["wo"], cdt, tag="attn/wo")
    if "bo" in p:
        y = y + p["bo"].astype(cdt)

    # --- default placement: adapter on the attention block output ---
    if adapter is not None and acfg.kind == "hadamard" and acfg.position == "attn_out":
        y = apply_hadamard(y, adapter)

    return y, new_cache


# ---------------------------------------------------------------------------
# Cache write and read (shared with latent attention, models/mla.py)
# ---------------------------------------------------------------------------


def write_read_cache(cache, new, layer, write_pos, *, window=None,
                     block_tables=None, paged_kv_len=None, dtype=None):
    """Decode's cache step for one attention slot: write the new tokens'
    entries into the layer group's stacked cache at `layer` and read that
    layer's view back. Returns (new cache, views, kv_pos, kv_len) for
    `flash.attend`.

    `new` maps each cache leaf's name to its (B, S, ...) entries: `k` and
    `v` (B, S, KH, Dh) for GQA, or one `latent` (B, S, mla.cache_dim)
    that latent attention reads as a single KV head.
    Paged (`block_tables` (B, nbt)): each leaf is a pool (repeats,
    num_blocks, page, ...), plain or a QTensor, written through the
    tables and gathered to (B, nbt*page, ...) in `dtype`; `paged_kv_len`
    overrides the valid length (extend). Contiguous: each leaf is
    (repeats, B, size, ...) and is read as stored.
    write_pos: scalar (all rows), (B,) per-row, or (B, S) per-row-per-
    token (speculative verify, paged extend) absolute positions. With a
    `window` the layer keeps a ring of that size (paged: in the first
    window//page table entries) and validity is entirely positional.
    """
    wp = jnp.asarray(write_pos, jnp.int32)
    if block_tables is not None:
        first = next(iter(cache.values()))
        vals = first.values if is_qtensor(first) else first
        B, page = block_tables.shape[0], vals.shape[2]
        size = block_tables.shape[1] * page  # gathered logical length
        wp2 = wp if wp.ndim == 2 else wp[:, None]  # (B, S) logical positions
        if window is None:
            li = wp2
            kv_pos = jnp.arange(size)
            if paged_kv_len is not None:
                eff_len = paged_kv_len
            else:
                # (B, S) write_pos is a speculative verify: the valid
                # length runs to the LAST write, per-query causal masking
                # hides the later writes from the earlier queries
                eff_len = (wp[:, -1] if wp.ndim == 2 else wp) + 1
        else:
            # ring layout inside the first ring//page table entries; the
            # gathered tail beyond the ring carries INVALID_POS so validity
            # is entirely positional (scheduler guarantees page | ring)
            ring = min(window, size)
            li = wp2 % ring
            rp = ring_positions(ring, wp[:, -1] if wp.ndim == 2 else wp)
            kv_pos = jnp.concatenate(
                [rp, jnp.full((B, size - ring), INVALID_POS, jnp.int32)],
                axis=1) if size > ring else rp
            eff_len = INVALID_POS
        bidx = jnp.arange(B)[:, None]
        at = (layer, block_tables[bidx, li // page], li % page)
        with scope("repro.kv_write"):
            if is_qtensor(first):
                # per-token-per-head scales, computed independently at each
                # write (absmax over the last dim) - the pool's scales layout
                mode = "int8" if vals.dtype == jnp.int8 else "fp8"
                qs = {n: quantize(x, mode, axis=-1) for n, x in new.items()}
                out = {n: QTensor(cache[n].values.at[at].set(q.values),
                                  cache[n].scales.at[at].set(q.scales))
                       for n, q in qs.items()}
            else:
                out = {n: cache[n].at[at].set(x.astype(cache[n].dtype))
                       for n, x in new.items()}
        views = {n: flash.paged_gather(out[n], layer, block_tables, dtype)
                 for n in new}
        return out, views, kv_pos, eff_len

    size = next(iter(cache.values())).shape[2]
    slot_idx = wp % size
    B = next(iter(new.values())).shape[0]
    with scope("repro.kv_write"):
        if wp.ndim == 2:  # (B, S) per-row-per-token: speculative verify
            at = (layer, jnp.arange(B)[:, None], slot_idx)
            out = {n: cache[n].at[at].set(x.astype(cache[n].dtype))
                   for n, x in new.items()}
        elif wp.ndim:  # (B,) per-row write positions (continuous batching)
            at = (layer, jnp.arange(B), slot_idx)
            out = {n: cache[n].at[at].set(x[:, 0].astype(cache[n].dtype))
                   for n, x in new.items()}
        else:
            out = {n: jax.lax.dynamic_update_slice(
                cache[n], x[None].astype(cache[n].dtype),
                (layer, 0, slot_idx) + (0,) * (x.ndim - 2))
                for n, x in new.items()}
    last = wp[:, -1] if wp.ndim == 2 else wp  # last write per row
    if window is None:
        kv_pos = jnp.arange(size)
        eff_len = last + 1  # scalar, or (B,) per-row valid lengths
    else:
        kv_pos = ring_positions(size, last)
        eff_len = INVALID_POS  # validity entirely via positions
    return out, {n: out[n][layer] for n in new}, kv_pos, eff_len


def prefill_cache(new, cache_len: int, window=None):
    """The cache a prefill of S tokens builds from each leaf's (B, S, ...)
    entries: (B, size, ...) with size = cache_len, or the window when
    smaller (a ring, slot p % size holding position p)."""
    S = next(iter(new.values())).shape[1]
    size = cache_len if window is None else min(window, cache_len)
    if window is None and size == S:
        return dict(new)
    tail = min(size, S)
    zeros = {n: jnp.zeros((x.shape[0], size) + x.shape[2:], x.dtype)
             for n, x in new.items()}
    if window is None:
        return {n: jax.lax.dynamic_update_slice_in_dim(
            zeros[n], x[:, S - tail:], S - tail, axis=1)
            for n, x in new.items()}
    slots = jnp.arange(S - tail, S) % size
    return {n: zeros[n].at[:, slots].set(x[:, S - tail:])
            for n, x in new.items()}
