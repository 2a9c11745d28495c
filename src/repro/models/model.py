"""Top-level model families built on the layer program.

Families:
  decoder  - causal LM (starcoder2, qwen3, gemma2, deepseek/qwen3 MoE,
             rwkv6, recurrentgemma)
  encoder  - BERT/RoBERTa-style classifier (the paper's PLMs): learned
             positions, segment embeddings, post-LN, pooler + classifier
  encdec   - Whisper backbone: audio-frame-embedding encoder (conv frontend
             stubbed per task spec) + causal decoder w/ cross-attention
  vlm      - InternVL backbone: precomputed patch embeddings (ViT stubbed)
             prepended to the token sequence of a decoder LM

All functions are pure: (params, cfg, inputs) -> outputs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.types import ModelCfg
from repro.dist.api import constrain
from repro.models.layers import apply_norm, dense_init, embed_init, norm_init, softcap
from repro.models.program import (
    group_apply,
    group_cache_init,
    group_init,
    group_pool_init,
)
from repro.obs.profile import scope
from repro.quant.qtensor import qdense

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelCfg):
    ks = jax.random.split(key, 16)
    p = {"embed": {"table": embed_init(ks[0], cfg.vocab_size, cfg.d_model, cfg.pdtype)}}

    if cfg.pos == "learned":
        p["pos_embed"] = {"table": embed_init(ks[1], cfg.max_seq_len, cfg.d_model, cfg.pdtype)}
    if cfg.n_segment_types:
        p["type_embed"] = {"table": embed_init(ks[2], cfg.n_segment_types, cfg.d_model, cfg.pdtype)}
        p["embed_norm"] = norm_init(cfg)

    p["blocks"] = {
        f"g{i}": group_init(jax.random.fold_in(ks[3], i), cfg, g)
        for i, g in enumerate(cfg.groups)
    }
    p["final_norm"] = norm_init(cfg)

    if cfg.enc_groups:
        p["enc_blocks"] = {
            f"g{i}": group_init(jax.random.fold_in(ks[4], i), cfg, g)
            for i, g in enumerate(cfg.enc_groups)
        }
        p["enc_final_norm"] = norm_init(cfg)
        p["enc_pos_embed"] = {
            "table": embed_init(ks[5], cfg.n_audio_frames, cfg.d_model, cfg.pdtype)
        }

    if cfg.family == "vlm":
        p["vlm_proj"] = {"kernel": dense_init(ks[6], cfg.d_model, cfg.d_model, cfg.pdtype)}

    if cfg.family == "encoder":
        p["pooler"] = {
            "kernel": dense_init(ks[7], cfg.d_model, cfg.d_model, cfg.pdtype),
            "bias": jnp.zeros((cfg.d_model,), cfg.pdtype),
        }
        p["classifier"] = {
            "kernel": dense_init(ks[8], cfg.d_model, cfg.n_classes, jnp.float32),
            "bias": jnp.zeros((cfg.n_classes,), jnp.float32),
        }
    elif not cfg.tie_embeddings:
        p["lm_head"] = {"kernel": dense_init(ks[9], cfg.d_model, cfg.vocab_size, cfg.pdtype)}
    return p


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(params, cfg: ModelCfg, tokens, positions=None, type_ids=None):
    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, cfg.cdtype)
    if cfg.pos == "learned" and positions is not None:
        x = x + jnp.take(params["pos_embed"]["table"], positions, axis=0).astype(cfg.cdtype)
    if cfg.n_segment_types and type_ids is not None:
        x = x + jnp.take(params["type_embed"]["table"], type_ids, axis=0).astype(cfg.cdtype)
    if "embed_norm" in params:
        x = apply_norm(params["embed_norm"], cfg, x)
    return x


@scope("repro.lm_head")
def lm_logits(params, cfg: ModelCfg, h):
    if cfg.tie_embeddings:
        # the embed table stays dense (it is a gather path, not a matmul
        # weight - see quant.QUANT_PATTERNS), so tied logits do too
        logits = h @ params["embed"]["table"].astype(cfg.cdtype).T
    else:
        logits = qdense(h, params["lm_head"]["kernel"], cfg.cdtype,
                        tag="lm_head")
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return constrain(logits, "dp", None, "model")


# ---------------------------------------------------------------------------
# Backbone driver
# ---------------------------------------------------------------------------


def _run_groups(params, cfg: ModelCfg, groups, blocks_key, x, *, q_pos, causal,
                mode="train", caches=None, cache_len=None, write_pos=None,
                enc_out=None, block_tables=None, paged_kv_len=None):
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {}
    for i, g in enumerate(groups):
        x, nc, aux = group_apply(
            params[blocks_key][f"g{i}"], cfg, g, x,
            q_pos=q_pos, causal=causal, mode=mode,
            caches=(caches or {}).get(f"g{i}"), cache_len=cache_len,
            write_pos=write_pos, enc_out=enc_out,
            block_tables=block_tables, paged_kv_len=paged_kv_len,
        )
        if nc is not None:
            new_caches[f"g{i}"] = nc
        aux_total = aux_total + aux
    return x, (new_caches or None), aux_total


# ---------------------------------------------------------------------------
# decoder / vlm family
# ---------------------------------------------------------------------------


def _decoder_embed(params, cfg: ModelCfg, tokens, patches=None):
    S_txt = tokens.shape[1]
    pos_txt = jnp.arange(S_txt)
    if cfg.family == "vlm" and patches is not None:
        img = qdense(patches.astype(cfg.cdtype), params["vlm_proj"]["kernel"],
                     cfg.cdtype, tag="vlm_proj")
        txt = embed_tokens(params, cfg, tokens, positions=pos_txt)
        x = jnp.concatenate([img, txt], axis=1)
    else:
        x = embed_tokens(params, cfg, tokens, positions=pos_txt)
    return constrain(x, "dp", None, None)


def forward_hidden(params, cfg: ModelCfg, tokens, patches=None):
    """Final-norm hidden states (training); logits left to the caller so
    the loss can compute them in sequence chunks (cfg.ce_chunk)."""
    x = _decoder_embed(params, cfg, tokens, patches)
    q_pos = jnp.arange(x.shape[1])
    x, _, aux = _run_groups(params, cfg, cfg.groups, "blocks", x,
                            q_pos=q_pos, causal=True, mode="train")
    return apply_norm(params["final_norm"], cfg, x), aux


def forward_lm(params, cfg: ModelCfg, tokens, patches=None):
    """Teacher-forced full-sequence logits (training)."""
    x, aux = forward_hidden(params, cfg, tokens, patches)
    return lm_logits(params, cfg, x), aux


def prefill_lm(params, cfg: ModelCfg, tokens, cache_len: int, patches=None,
               last_pos=None):
    """last_pos: position whose logits to return (default: the final one).
    A traced last_pos lets right-padded prompts share one compiled shape
    (prompt-length bucketing): under causal masking the pad suffix never
    influences positions <= last_pos, and decode overwrites/masks the
    padded cache entries before they are ever attended."""
    x = _decoder_embed(params, cfg, tokens, patches)
    q_pos = jnp.arange(x.shape[1])
    x, caches, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                               q_pos=q_pos, causal=True, mode="prefill",
                               cache_len=cache_len)
    if last_pos is None:
        x = x[:, -1:]
    else:
        x = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params, cfg, x), caches


def decode_lm(params, cfg: ModelCfg, caches, token, pos):
    """One decode step. token: (B, 1) int32; pos: scalar int32 shared by
    every row, or (B,) int32 per-row absolute positions (continuous
    batching: each cache row is an independent request mid-sequence)."""
    pos = jnp.asarray(pos, jnp.int32)
    x = embed_tokens(params, cfg, token)
    q_pos = pos[:, None] if pos.ndim else jnp.full((1,), pos, jnp.int32)
    x, caches, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                               q_pos=q_pos, causal=True, mode="decode",
                               caches=caches, write_pos=pos)
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params, cfg, x), caches


def verify_lm(params, cfg: ModelCfg, caches, tokens, pos):
    """Speculative-decoding verify: score S = k+1 tokens per row in ONE
    decode-mode forward. tokens: (B, S) int32 = [last accepted token,
    k draft tokens]; pos: (B,) absolute position of tokens[:, 0].

    Writes K/V at positions pos+j for every j (per-row multi-position
    scatter), overwriting any stale rejected-draft entries left by the
    previous tick - the scheduler guarantees the new write range covers
    them, and per-query causal masking hides positions > pos+j from
    query j inside this forward. Returns logits for ALL S positions:
    logits[:, j] is the target distribution for position pos+j+1 given
    tokens[:, :j+1], so greedy argmax over column j reproduces plain
    one-token decode exactly (the acceptance rule's token-identity
    guarantee). Full-attention slots only: a ring window evicts entries
    the earlier queries still need (the scheduler validates)."""
    _no_verify_for_mla(cfg)
    pos = jnp.asarray(pos, jnp.int32)
    S = tokens.shape[1]
    qp = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # (B, S)
    x = embed_tokens(params, cfg, tokens)
    x, caches, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                               q_pos=qp, causal=True, mode="decode",
                               caches=caches, write_pos=qp)
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params, cfg, x), caches


def _no_verify_for_mla(cfg: ModelCfg) -> None:
    if cfg.mla is not None:
        raise ValueError("speculative verify is not supported for latent "
                         "attention (cfg.mla); serve it without spec_k")


def init_decode_caches(cfg: ModelCfg, batch: int, cache_len: int):
    return {
        f"g{i}": group_cache_init(cfg, g, batch, cache_len)
        for i, g in enumerate(cfg.groups)
    }


# ---------------------------------------------------------------------------
# paged KV cache (block pool + block tables, serving/paged.py)
# ---------------------------------------------------------------------------


def init_paged_pool(cfg: ModelCfg, num_blocks: int, page: int,
                    quant=None):
    """One device-resident block pool per attention slot: K/V leaves
    (repeats, num_blocks, page, KH, Dh), or for latent attention one
    `latent` leaf (repeats, num_blocks, page, mla.cache_dim).
    Block 0 is the reserved null block (see program.group_pool_init)."""
    return {
        f"g{i}": group_pool_init(cfg, g, num_blocks, page, quant=quant)
        for i, g in enumerate(cfg.groups)
    }


def decode_lm_paged(params, cfg: ModelCfg, pool, token, pos, block_tables):
    """One paged decode step: like `decode_lm` but each row's KV lives in
    pool blocks addressed through its `block_tables` row (B, nbt). pos is
    (B,) per-row absolute positions; rows whose table is all-null (free
    slots) write into block 0 and their logits are garbage the scheduler
    ignores. nbt*page must equal the contiguous cache length it replaces
    so the flash kv-chunk decomposition (and therefore every fp32 token)
    is identical."""
    pos = jnp.asarray(pos, jnp.int32)
    x = embed_tokens(params, cfg, token)
    q_pos = pos[:, None] if pos.ndim else jnp.full((1,), pos, jnp.int32)
    x, pool, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                             q_pos=q_pos, causal=True, mode="decode",
                             caches=pool, write_pos=pos,
                             block_tables=block_tables)
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params, cfg, x), pool


def verify_lm_paged(params, cfg: ModelCfg, pool, tokens, pos, block_tables):
    """`verify_lm` against the paged block pool: K/V for the k+1 scored
    positions land in the pool blocks the table maps pos+j to (the
    scheduler pre-allocates every page the write range touches), and the
    gathered view masks by the LAST write's valid length with per-query
    causal masking below it - same rollback-by-overwrite contract as the
    contiguous path."""
    _no_verify_for_mla(cfg)
    pos = jnp.asarray(pos, jnp.int32)
    S = tokens.shape[1]
    qp = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # (B, S)
    x = embed_tokens(params, cfg, tokens)
    x, pool, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                             q_pos=qp, causal=True, mode="decode",
                             caches=pool, write_pos=qp,
                             block_tables=block_tables)
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params, cfg, x), pool


def extend_lm(params, cfg: ModelCfg, pool, tokens, block_tables, start,
              kv_len, last_pos):
    """Prefix-cache partial-hit extension (B=1): run only the prompt
    suffix `tokens` (right-padded to a page multiple) at absolute
    positions start..start+S-1, writing its K/V into the pool blocks the
    table maps those positions to, attending over shared prefix blocks +
    own suffix. kv_len masks the pad tail (decode overwrites each padded
    position before kv_len ever unmasks it - the prompt-bucketing
    argument); last_pos indexes the last real suffix token's logits.
    Full-attention only: ring layouts fold pad tokens in."""
    pos = start + jnp.arange(tokens.shape[1])[None, :]  # (1, S) absolute
    x = embed_tokens(params, cfg, tokens, positions=pos)
    x, pool, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                             q_pos=pos, causal=True, mode="decode",
                             caches=pool, write_pos=pos,
                             block_tables=block_tables, paged_kv_len=kv_len)
    x = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params, cfg, x), pool


# ---------------------------------------------------------------------------
# encoder (BERT/RoBERTa) family
# ---------------------------------------------------------------------------


def forward_encoder(params, cfg: ModelCfg, tokens, type_ids=None):
    """Returns (cls_logits, pooled, sequence_h)."""
    B, S = tokens.shape
    pos = jnp.arange(S)
    x = embed_tokens(params, cfg, tokens, positions=pos, type_ids=type_ids)
    x = constrain(x, "dp", None, None)
    x, _, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                          q_pos=pos, causal=False, mode="train")
    pooled = jnp.tanh(
        x[:, 0] @ params["pooler"]["kernel"].astype(cfg.cdtype)
        + params["pooler"]["bias"].astype(cfg.cdtype)
    )
    logits = (pooled.astype(jnp.float32) @ params["classifier"]["kernel"]
              + params["classifier"]["bias"])
    return logits, pooled, x


# ---------------------------------------------------------------------------
# encdec (Whisper backbone) family
# ---------------------------------------------------------------------------


def encode_audio(params, cfg: ModelCfg, frames):
    """frames: (B, n_frames, d) precomputed conv-frontend embeddings (stub)."""
    S = frames.shape[1]
    pos = jnp.arange(S)
    x = frames.astype(cfg.cdtype) + jnp.take(
        params["enc_pos_embed"]["table"], pos, axis=0).astype(cfg.cdtype)
    x, _, _ = _run_groups(params, cfg, cfg.enc_groups, "enc_blocks", x,
                          q_pos=pos, causal=False, mode="train")
    return apply_norm(params["enc_final_norm"], cfg, x)


def forward_encdec(params, cfg: ModelCfg, frames, tokens):
    enc = encode_audio(params, cfg, frames)
    S = tokens.shape[1]
    pos = jnp.arange(S)
    x = embed_tokens(params, cfg, tokens, positions=pos)
    x, _, aux = _run_groups(params, cfg, cfg.groups, "blocks", x,
                            q_pos=pos, causal=True, mode="train", enc_out=enc)
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params, cfg, x), aux


def prefill_encdec(params, cfg: ModelCfg, frames, tokens, cache_len: int):
    enc = encode_audio(params, cfg, frames)
    S = tokens.shape[1]
    pos = jnp.arange(S)
    x = embed_tokens(params, cfg, tokens, positions=pos)
    x, caches, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                               q_pos=pos, causal=True, mode="prefill",
                               cache_len=cache_len, enc_out=enc)
    x = apply_norm(params["final_norm"], cfg, x[:, -1:])
    return lm_logits(params, cfg, x), caches


def decode_encdec(params, cfg: ModelCfg, caches, token, pos):
    """pos: scalar, or (B,) per-row positions (see decode_lm)."""
    pos = jnp.asarray(pos, jnp.int32)
    q_pos = pos[:, None] if pos.ndim else jnp.full((1,), pos, jnp.int32)
    x = embed_tokens(params, cfg, token, positions=q_pos)
    x, caches, _ = _run_groups(params, cfg, cfg.groups, "blocks", x,
                               q_pos=q_pos, causal=True, mode="decode",
                               caches=caches, write_pos=pos)
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params, cfg, x), caches


def init_encdec_caches(cfg: ModelCfg, batch: int, cache_len: int):
    return {
        f"g{i}": group_cache_init(cfg, g, batch, cache_len,
                                  enc_len=cfg.n_audio_frames)
        for i, g in enumerate(cfg.groups)
    }
