"""Pallas TPU kernel for the RWKV6 WKV recurrence.

Tiling: grid = (B*H, nt) with the time-chunk axis innermost. The per-head
matrix state S (n x n, fp32) lives in VMEM scratch and persists across the
sequential chunk sweep; each chunk of L timesteps streams (L, n) tiles of
r/k/v/w through VMEM and runs the recurrence with a fori_loop. This keeps
HBM traffic at O(T*n) per head (r,k,v,w read once, o written once) and the
state resident in VMEM - the TPU adaptation of the paper-family's CUDA
wkv kernels. A production variant would use the chunked matmul form for
MXU utilization; this kernel is the memory-hierarchy-correct scaffold the
tests validate against ref.wkv6_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, L: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0].astype(jnp.float32)  # (1, n)
    n = u.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def col(row):
        # (1, n) -> (n, 1) by a masked lane reduction: Mosaic has no
        # vector-matrix dot and no cheap transpose at n = 64
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def step(i, _):
        t_ = pl.ds(i, 1)
        r = r_ref[0, t_].astype(jnp.float32)  # (1, n)
        k = k_ref[0, t_].astype(jnp.float32)
        v = v_ref[0, t_].astype(jnp.float32)
        w = w_ref[0, t_].astype(jnp.float32)
        S = s_scr[...]
        # o_j = sum_i r_i S_ij + (sum_i r_i u_i k_i) v_j
        o = (jnp.sum(col(r) * S, axis=0, keepdims=True)
             + jnp.sum(r * u * k) * v)
        s_scr[...] = col(w) * S + col(k) * v
        o_ref[0, t_] = o
        return 0

    jax.lax.fori_loop(0, L, step, 0)


def wkv6_tpu(r, k, v, w, u, *, chunk: int = 64, interpret: bool):
    """r,k,v,w: (B,H,T,n); u: (H,n). Returns o: (B,H,T,n). Zero init state."""
    B, H, T, n = r.shape
    BH = B * H
    L = min(chunk, T)
    nt = (T + L - 1) // L

    def flat(x):
        # fp32 in HBM (and out): the recurrence reads and writes one
        # timestep row at a dynamic offset, which Mosaic does only on an
        # unpacked (32-bit) tile
        return x.reshape(BH, T, n).astype(jnp.float32)

    # (BH, 1, n): the per-head block (1, 1, n) blocks only the leading axis
    u_flat = jnp.broadcast_to(u[None], (B, H, n)).reshape(BH, 1, n)

    kern = functools.partial(_kernel, L=L)
    o = pl.pallas_call(
        kern,
        grid=(BH, nt),
        in_specs=[
            pl.BlockSpec((1, L, n), lambda bh, t: (bh, t, 0)),
            pl.BlockSpec((1, L, n), lambda bh, t: (bh, t, 0)),
            pl.BlockSpec((1, L, n), lambda bh, t: (bh, t, 0)),
            pl.BlockSpec((1, L, n), lambda bh, t: (bh, t, 0)),
            pl.BlockSpec((1, 1, n), lambda bh, t: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, L, n), lambda bh, t: (bh, t, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(flat(r), flat(k), flat(v), flat(w), u_flat)
    return o.reshape(B, H, T, n).astype(r.dtype)
