"""Pallas TPU flash-attention forward kernel.

Tiling: grid = (B, KH*G, nq, nk) with the kv-block axis innermost; running
(m, l, acc) state lives in VMEM scratch and persists across the sequential
nk sweep (the canonical TPU flash pattern - the MXU sees (bq, D) x (D, bk)
tiles, the VPU does the rescaling). GQA is handled in the index map: query
head h reads kv head h // G, so grouped K/V blocks are fetched once per
group without materializing repeats.

Supports causal and local-window masking and gemma2 logit soft-capping.
This is the TPU fast path; the portable chunked implementation with the
custom VJP lives in repro.models.flash, and the dense oracle in ref.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, cap: float, causal: bool, window: Optional[int],
            bq: int, bk: int, nk: int, sq: int, skv: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap:
        s = jnp.tanh(s / cap) * cap

    # right-aligned absolute positions (self-attention, same offset)
    qp = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (skv - sq)
    kp = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kp < skv
    if causal:
        valid &= kp <= qp
    if window is not None:
        valid &= qp - kp < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention_tpu(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None, cap: float = 0.0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool):
    """q: (B, H, Sq, D); k,v: (B, KH, Skv, D) with H = KH*G. Forward only."""
    B, H, Sq, D = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale if scale is not None else D**-0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    nq = (Sq + bq - 1) // bq
    nk = (Skv + bk - 1) // bk

    kern = functools.partial(
        _kernel, scale=float(scale), cap=float(cap), causal=causal,
        window=window, bq=bq, bk=bk, nk=nk, sq=Sq, skv=Skv)

    return pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            # GQA in the index map: query head h reads kv head h // G
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Paged decode attention (block-table gather via scalar prefetch)
# ---------------------------------------------------------------------------


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  scale: float, cap: float, window: Optional[int],
                  page: int, nbt: int, ring: int, sq: int, group: int,
                  quant: bool):
    """Sq decode tokens per sequence; grid (B, H, nbt), kv-block innermost.

    The block table never reaches the kernel body's data path: it is a
    scalar-prefetch argument consumed by the K/V BlockSpec index maps, so
    each grid step DMAs exactly the physical page the table names - the
    gather IS the pipeline. len_ref carries the per-row valid length
    through the LAST query (linear) or the last query's write position
    (ring window, validity entirely positional); for sq > 1 (speculative
    multi-token verify) each query i sits at the right-aligned position
    len - sq + i and masks per-query. With `quant`, K/V pages arrive int8
    alongside their per-token scale pages and are widened in-register
    before the MXU.
    """
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    kh = pl.program_id(1) // group

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (sq, D)
    k = k_ref[0].astype(jnp.float32)  # (page, D): kv head kh's columns
    v = v_ref[0].astype(jnp.float32)
    if quant:
        # scale pages arrive whole (page, KH); pick kv head kh's column
        # with a one-hot reduction - no dynamic lane slice
        onehot = jax.lax.broadcasted_iota(
            jnp.int32, ks_ref.shape[1:], 1) == kh
        k = k * jnp.sum(jnp.where(onehot, ks_ref[0].astype(jnp.float32),
                                  0.0), axis=1, keepdims=True)
        v = v * jnp.sum(jnp.where(onehot, vs_ref[0].astype(jnp.float32),
                                  0.0), axis=1, keepdims=True)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap:
        s = jnp.tanh(s / cap) * cap

    # li: logical index into the gathered sequence this page covers;
    # qi: query row index (query i's absolute position is right-aligned)
    li = j * page + jax.lax.broadcasted_iota(jnp.int32, (sq, page), 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, (sq, page), 0)
    if window is None:
        qpos = len_ref[b] - sq + qi  # per-query valid prefix: li <= qpos
        valid = li <= qpos
    else:
        # ring layout in the first `ring` logical slots: slot li holds the
        # latest position p <= wp_last with p % ring == li; the causal
        # bound p <= qpos hides the later queries' overwrites from the
        # earlier queries, and ring <= window makes the window bound
        # automatic (qpos - p < ring whenever p <= qpos)
        wp = len_ref[b]
        p = wp - ((wp - li) % ring)
        qpos = wp - (sq - 1) + qi
        valid = (li < ring) & (p >= 0) & (p <= qpos)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p_ = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p_.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p_, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nbt - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def paged_attention_tpu(q, k_pool, v_pool, tables, kv_lens, *,
                        window: Optional[int] = None,
                        scale: Optional[float] = None, cap: float = 0.0,
                        k_scales=None, v_scales=None,
                        interpret: bool):
    """Paged decode attention. q: (B, H, D) - one token per sequence - or
    (B, H, Sq, D) for a speculative multi-token verify (right-aligned
    queries, per-query causal masks); k_pool/v_pool: (num_blocks, page,
    KH, D) block pools (int8 when k_scales/v_scales (num_blocks, page,
    KH, 1) are given); tables: (B, nbt) int32 physical block ids;
    kv_lens: (B,) int32 valid length through the last query (linear) or
    the last query's write position (windowed). Forward only - the
    decode path never differentiates.

    The TPU block-shape rule wants the last two dims of every block to be
    (8, 128)-aligned or whole, so the pools are viewed as (num_blocks,
    page, KH*D) and kv head h // G is the D-wide column block of a
    (1, page, D) block; the scale pools are viewed as (num_blocks, page,
    KH) and fetched a whole page at a time."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, :, None]  # one query: (B, H, 1, D)
    B, H, sq, D = q.shape
    N, page, KH = k_pool.shape[:3]
    nbt = tables.shape[1]
    G = H // KH
    scale = scale if scale is not None else D**-0.5
    size = nbt * page
    ring = min(window, size) if window is not None else size
    quant = k_scales is not None

    kern = functools.partial(
        _paged_kernel, scale=float(scale), cap=float(cap), window=window,
        page=page, nbt=nbt, ring=ring, sq=sq, group=G, quant=quant)

    kv_spec = pl.BlockSpec(
        (1, page, D), lambda b, h, j, tbl, kl: (tbl[b, j], 0, h // G))
    sc_spec = pl.BlockSpec(
        (1, page, KH), lambda b, h, j, tbl, kl: (tbl[b, j], 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, sq, D), lambda b, h, j, tbl, kl: (b, h, 0, 0)),
        kv_spec, kv_spec,
    ]
    args = [tables.astype(jnp.int32), kv_lens.astype(jnp.int32), q,
            k_pool.reshape(N, page, KH * D), v_pool.reshape(N, page, KH * D)]
    if quant:
        in_specs += [sc_spec, sc_spec]
        args += [k_scales.reshape(N, page, KH), v_scales.reshape(N, page, KH)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nbt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, sq, D),
                               lambda b, h, j, tbl, kl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((sq,), jnp.float32),
            pltpu.VMEM((sq,), jnp.float32),
            pltpu.VMEM((sq, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, sq, D), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:, :, 0] if squeeze else out
