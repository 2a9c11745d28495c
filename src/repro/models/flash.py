"""Chunked (flash-style) attention in pure jnp with a custom VJP.

This is the portable production path: O(chunk) memory in both forward and
backward (the VJP recomputes tiles instead of storing the S x S probability
matrices), correct GQA grouping, causal + local-window masking and gemma2
logit soft-capping.  The Pallas TPU kernel in `repro.kernels.attention`
implements the same tiling for the MXU; `repro.kernels.ref` holds the dense
oracle both are tested against.

Shapes:
  q:        (B, Sq, KH, G, D)   - G = query heads per kv head
  k, v:     (B, Skv, KH, D), (B, Skv, KH, Dv) - the value width may
            differ from the key width (latent attention's expanded
            prefill: 192 vs 128)
  q_pos:    (Sq,) int32 absolute positions of the queries
  kv_pos:   (Skv,) int32 absolute positions of the keys
  kv_len:   scalar int32 - number of valid kv entries (for decode caches)
Returns:    (B, Sq, KH, G, Dv)

Per-sequence positions (continuous batching): q_pos may be (B, Sq),
kv_pos (B, Skv) and kv_len (B,) so every row of the batch attends at its
own absolute position over its own valid cache prefix - the shape the
slot-based decode tick in `repro.serving.scheduler` runs every step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.costmode import scan_unroll
from repro.obs.profile import scope

NEG_INF = -1e30


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _pad_to(x, size: int, axis: int):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_kv(x, size: int):
    """K or V padded along the sequence to whole kv chunks. Over a decode's
    max_len cache view this copies the whole view, so the pad is named
    with the gather that built the view (`repro.kv_gather`); a prefill or
    train step whose length is not a whole number of chunks shows its pad
    under that name too."""
    with scope("repro.kv_gather"):
        return _pad_to(x, size, 1)


def _mask(q_pos, kv_pos, kv_len, causal: bool, window: Optional[int]):
    """Bool validity mask: (Sq, Skv), or (B, Sq, Skv) when any of q_pos
    (B, Sq) / kv_pos (B, Skv) / kv_len (B,) carries a batch dim."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    kl = jnp.asarray(kv_len)
    if kl.ndim:
        kl = kl[..., None, None]
    m = kp < kl  # cache validity / padding
    if causal:
        m = m & (kp <= qp)
    if window is not None:
        m = m & (qp - kp < window)
    return m


def _expand_mask(valid):
    """Broadcast a (qc, kc) or (B, qc, kc) tile mask over (B, KH, G, qc, kc)."""
    if valid.ndim == 2:
        return valid[None, None, None]
    return valid[:, None, None]


def _tile_scores(q_i, k_j, scale, cap, tile_dtype=jnp.float32):
    """Scores for one (q-chunk, kv-chunk) tile: matmul inputs in
    `tile_dtype` (bf16 on the MXU), fp32 accumulation/output."""
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", q_i.astype(tile_dtype), k_j.astype(tile_dtype),
        preferred_element_type=jnp.float32,
    )
    s = s * scale
    if cap:
        s = jnp.tanh(s / cap) * cap
    return s  # (B, KH, G, qc, kc)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _flash_fwd_impl(q, k, v, q_pos, kv_pos, kv_len, causal, window, scale, cap,
                    q_chunk, kv_chunk, tile_dtype=jnp.float32):
    B, Sq, KH, G, D = q.shape
    Dv = v.shape[-1]
    Skv = k.shape[1]
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    nq, nk = _cdiv(Sq, qc), _cdiv(Skv, kc)

    batched_pos = q_pos.ndim == 2 or kv_pos.ndim == 2
    qp = _pad_to(q_pos, nq * qc, q_pos.ndim - 1)
    kp = jnp.where(
        jnp.arange(nk * kc) < Skv, _pad_to(kv_pos, nk * kc, kv_pos.ndim - 1),
        jnp.iinfo(jnp.int32).max
    )
    q_r = _pad_to(q, nq * qc, 1).reshape(B, nq, qc, KH, G, D).transpose(1, 0, 2, 3, 4, 5)
    k_r = _pad_kv(k, nk * kc).reshape(B, nk, kc, KH, D).transpose(1, 0, 2, 3, 4)
    v_r = _pad_kv(v, nk * kc).reshape(B, nk, kc, KH, Dv).transpose(1, 0, 2, 3, 4)
    # chunk-index-leading position tiles: (nq, qc) / (nq, B, qc) etc.
    qp_r = (qp.reshape(B, nq, qc).transpose(1, 0, 2) if q_pos.ndim == 2
            else qp.reshape(nq, qc))
    kp_r = (kp.reshape(B, nk, kc).transpose(1, 0, 2) if kv_pos.ndim == 2
            else kp.reshape(nk, kc))

    # Local-window fast path: each q chunk only ever sees keys in
    # [q_start - window + 1, q_end], i.e. at most n_win kv chunks. Slicing
    # that band (dynamic_slice with a traced start) turns O(S^2) local
    # attention into O(S*window): 16x fewer tiles for recurrentgemma's
    # window-2048 layers at 32k prefill. Requires one shared position per
    # q chunk, so per-sequence (batched) positions take the generic path.
    n_win = nk
    if window is not None and causal:
        n_win = min(nk, _cdiv(window + qc - 1, kc) + 1)
    use_band = n_win < nk and not batched_pos
    k_flat = _pad_kv(k, nk * kc)
    v_flat = _pad_kv(v, nk * kc)

    def per_q(_, xs):
        q_i, qpos_i = xs

        if use_band:
            q_start = qpos_i[0]
            start = jnp.clip(q_start - (window - 1), 0, nk * kc - n_win * kc)
            k_band = jax.lax.dynamic_slice_in_dim(k_flat, start, n_win * kc, 1)
            v_band = jax.lax.dynamic_slice_in_dim(v_flat, start, n_win * kc, 1)
            kp_band = jax.lax.dynamic_slice_in_dim(kp, start, n_win * kc, 0)
            k_it = k_band.reshape(B, n_win, kc, KH, D).transpose(1, 0, 2, 3, 4)
            v_it = v_band.reshape(B, n_win, kc, KH, Dv).transpose(1, 0, 2, 3, 4)
            kp_it = kp_band.reshape(n_win, kc)
        else:
            k_it, v_it, kp_it = k_r, v_r, kp_r

        def inner(carry, kv):
            m, l, acc = carry
            k_j, v_j, kpos_j = kv
            s = _tile_scores(q_i, k_j, scale, cap, tile_dtype)
            valid = _mask(qpos_i, kpos_j, kv_len, causal, window)
            s = jnp.where(_expand_mask(valid), s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(tile_dtype),
                v_j.astype(tile_dtype), preferred_element_type=jnp.float32,
            )
            return (m_new, l, acc), None

        m0 = jnp.full((B, KH, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KH, G, qc, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            inner, (m0, l0, a0), (k_it, v_it, kp_it),
            unroll=scan_unroll(n_win if use_band else nk)
        )
        safe_l = jnp.where(l > 0, l, 1.0)
        out = (acc / safe_l[..., None]).transpose(0, 3, 1, 2, 4)  # (B,qc,KH,G,D)
        lse = m + jnp.log(safe_l)  # (B,KH,G,qc)
        return None, (out, lse)

    _, (out_r, lse_r) = jax.lax.scan(
        per_q, None, (q_r, qp_r), unroll=scan_unroll(nq)
    )
    out = out_r.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, KH, G, Dv)[:, :Sq]
    lse = lse_r.transpose(1, 2, 3, 0, 4).reshape(B, KH, G, nq * qc)[..., :Sq]
    return out.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Backward (recomputes tiles; no O(S^2) residuals)
# ---------------------------------------------------------------------------


def _flash_bwd_impl(res, g, causal, window, scale, cap, q_chunk, kv_chunk,
                    tile_dtype=jnp.float32):
    q, k, v, q_pos, kv_pos, kv_len, out, lse = res
    B, Sq, KH, G, D = q.shape
    Dv = v.shape[-1]
    Skv = k.shape[1]
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    nq, nk = _cdiv(Sq, qc), _cdiv(Skv, kc)

    g = g.astype(jnp.float32)
    delta = jnp.sum(g * out.astype(jnp.float32), axis=-1)  # (B,Sq,KH,G)

    qp = _pad_to(q_pos, nq * qc, q_pos.ndim - 1)
    kp = jnp.where(
        jnp.arange(nk * kc) < Skv, _pad_to(kv_pos, nk * kc, kv_pos.ndim - 1),
        jnp.iinfo(jnp.int32).max
    )
    q_r = _pad_to(q, nq * qc, 1).reshape(B, nq, qc, KH, G, D).transpose(1, 0, 2, 3, 4, 5)
    g_r = _pad_to(g, nq * qc, 1).reshape(B, nq, qc, KH, G, Dv).transpose(1, 0, 2, 3, 4, 5)
    dl_r = (
        _pad_to(delta, nq * qc, 1).reshape(B, nq, qc, KH, G).transpose(1, 0, 2, 3, 4)
    )
    lse_r = (
        _pad_to(lse, nq * qc, 3).reshape(B, KH, G, nq, qc).transpose(3, 0, 1, 2, 4)
    )
    k_r = _pad_to(k, nk * kc, 1).reshape(B, nk, kc, KH, D).transpose(1, 0, 2, 3, 4)
    v_r = _pad_to(v, nk * kc, 1).reshape(B, nk, kc, KH, Dv).transpose(1, 0, 2, 3, 4)
    qp_r = (qp.reshape(B, nq, qc).transpose(1, 0, 2) if q_pos.ndim == 2
            else qp.reshape(nq, qc))
    kp_r = (kp.reshape(B, nk, kc).transpose(1, 0, 2) if kv_pos.ndim == 2
            else kp.reshape(nk, kc))

    def tile_ds(q_i, k_j, qpos_i, kpos_j, lse_i, g_i, dl_i, v_j):
        """Recompute p for a tile and return (ds_raw, p)."""
        s_raw = jnp.einsum(
            "bqkgd,bskd->bkgqs", q_i.astype(tile_dtype), k_j.astype(tile_dtype),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.tanh(s_raw / cap) * cap if cap else s_raw
        valid = _mask(qpos_i, kpos_j, kv_len, causal, window)
        s = jnp.where(_expand_mask(valid), s, NEG_INF)
        p = jnp.exp(s - lse_i[..., None])  # (B,KH,G,qc,kc)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", g_i.astype(tile_dtype),
                        v_j.astype(tile_dtype),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - dl_i.transpose(0, 2, 3, 1)[..., None])
        if cap:
            t = jnp.tanh(s_raw / cap)
            ds = ds * (1.0 - jnp.square(t))
        ds = jnp.where(_expand_mask(valid), ds, 0.0)
        return ds, p

    # --- dQ: iterate q chunks, accumulate over kv chunks ---
    def per_q(_, xs):
        q_i, g_i, dl_i, lse_i, qpos_i = xs

        def inner(dq_acc, kv):
            k_j, v_j, kpos_j = kv
            ds, _ = tile_ds(q_i, k_j, qpos_i, kpos_j, lse_i, g_i, dl_i, v_j)
            dq_acc = dq_acc + jnp.einsum(
                "bkgqs,bskd->bqkgd", ds.astype(tile_dtype),
                k_j.astype(tile_dtype), preferred_element_type=jnp.float32,
            ) * scale
            return dq_acc, None

        dq0 = jnp.zeros((B, qc, KH, G, D), jnp.float32)
        dq_i, _ = jax.lax.scan(inner, dq0, (k_r, v_r, kp_r), unroll=scan_unroll(nk))
        return None, dq_i

    _, dq_r = jax.lax.scan(per_q, None, (q_r, g_r, dl_r, lse_r, qp_r),
                           unroll=scan_unroll(nq))
    dq = dq_r.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, KH, G, D)[:, :Sq]

    # --- dK, dV: iterate kv chunks, accumulate over q chunks ---
    def per_kv(_, xs):
        k_j, v_j, kpos_j = xs

        def inner(carry, qs):
            dk_acc, dv_acc = carry
            q_i, g_i, dl_i, lse_i, qpos_i = qs
            ds, p = tile_ds(q_i, k_j, qpos_i, kpos_j, lse_i, g_i, dl_i, v_j)
            dk_acc = dk_acc + jnp.einsum(
                "bkgqs,bqkgd->bskd", ds.astype(tile_dtype),
                q_i.astype(tile_dtype), preferred_element_type=jnp.float32,
            ) * scale
            dv_acc = dv_acc + jnp.einsum(
                "bkgqs,bqkgd->bskd", p.astype(tile_dtype),
                g_i.astype(tile_dtype), preferred_element_type=jnp.float32)
            return (dk_acc, dv_acc), None

        z = jnp.zeros((B, kc, KH, D), jnp.float32)
        zv = z if Dv == D else jnp.zeros((B, kc, KH, Dv), jnp.float32)
        (dk_j, dv_j), _ = jax.lax.scan(
            inner, (z, zv), (q_r, g_r, dl_r, lse_r, qp_r), unroll=scan_unroll(nq)
        )
        return None, (dk_j, dv_j)

    _, (dk_r, dv_r) = jax.lax.scan(per_kv, None, (k_r, v_r, kp_r),
                                   unroll=scan_unroll(nk))
    dk = dk_r.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, KH, D)[:, :Skv]
    dv = dv_r.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, KH, Dv)[:, :Skv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def flash_attention(q, k, v, q_pos, kv_pos, kv_len,
                    causal, window, scale, cap, q_chunk, kv_chunk,
                    tile_dtype_name="float32"):
    out, _ = _flash_fwd_impl(q, k, v, q_pos, kv_pos, kv_len,
                             causal, window, scale, cap, q_chunk, kv_chunk,
                             jnp.dtype(tile_dtype_name))
    return out


def _fwd(q, k, v, q_pos, kv_pos, kv_len, causal, window, scale, cap, q_chunk,
         kv_chunk, tile_dtype_name):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, kv_pos, kv_len,
                               causal, window, scale, cap, q_chunk, kv_chunk,
                               jnp.dtype(tile_dtype_name))
    return out, (q, k, v, q_pos, kv_pos, kv_len, out, lse)


def _bwd(causal, window, scale, cap, q_chunk, kv_chunk, tile_dtype_name,
         res, g):
    dq, dk, dv = _flash_bwd_impl(res, g, causal, window, scale, cap, q_chunk,
                                 kv_chunk, jnp.dtype(tile_dtype_name))
    return dq, dk, dv, None, None, None


flash_attention.defvjp(_fwd, _bwd)


def attend(q, k, v, *, q_pos, kv_pos, kv_len=None, causal=True, window=None,
           scale=None, cap=0.0, q_chunk=512, kv_chunk=1024,
           tile_dtype="float32"):
    """Convenience wrapper; kv_len defaults to Skv (all keys valid)."""
    if kv_len is None:
        kv_len = jnp.asarray(k.shape[1], jnp.int32)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return flash_attention(q, k, v, q_pos, kv_pos, kv_len,
                           causal, window, float(scale), float(cap),
                           int(q_chunk), int(kv_chunk), str(tile_dtype))


@scope("repro.kv_gather")
def paged_gather(pool, layer, tables, dtype):
    """Gather a per-sequence contiguous KV view out of a paged block pool.

    pool: a layer group's stacked (repeats, num_blocks, page, KH, Dh) pool,
    or a QTensor whose values share that shape with per-token-per-head
    scales (..., KH, 1), or an MLA slot's latent leaf (repeats,
    num_blocks, page, mla.cache_dim), gathered to (B, nbt*page,
    mla.cache_dim) for the caller to read as one KV head; `layer`
    picks the layer. One gather reads
    pool[layer, tables] without first slicing out pool[layer].
    tables: (B, nbt) int32 physical block ids (entry 0 is the null block -
    its rows are garbage and must be masked by the caller's kv_len /
    position masks). Returns (B, nbt*page, KH, Dh) in `dtype`, dequantized
    on the fly for QTensor pools - this gathered view is exactly what
    `attend` consumes, so the paged decode path reuses the flash kernel
    (and its kv-chunk decomposition) unchanged.
    """
    from repro.quant.qtensor import is_qtensor  # deferred: acyclic imports

    if is_qtensor(pool):
        g = (pool.values[layer, tables].astype(jnp.float32)
             * pool.scales[layer, tables].astype(jnp.float32))
    else:
        g = pool[layer, tables]
    B, nbt, page = g.shape[:3]
    return g.reshape(B, nbt * page, *g.shape[3:]).astype(dtype)
