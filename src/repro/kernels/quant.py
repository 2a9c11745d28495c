"""Pallas TPU kernel: fused dequantize + matmul for int8/fp8 weights.

Serving a quantized backbone must never materialize an fp32 copy of the
weights - the whole point is that HBM holds (and streams) 1 byte per
weight. The kernel loads an int8 (K, bn) weight block into VMEM, widens
and scales it there (per-output-channel scales: one (1, bn) vector per
block), and feeds the MXU directly:

    y[m-block, n-block] = x[m-block, :] @ (values[:, n-block] * scales[n-block])

Grid is (M-blocks, N-blocks); the contraction dim K stays whole inside a
block, so partial edge blocks need no masking: padded x rows / w cols only
influence output rows/cols that are themselves discarded. VMEM per step at
the default 128x128 blocks and K=8192 is ~4.2 MB fp32 x + ~1 MB int8 w -
inside the v5e budget with double buffering.

Backward (train-side QPEFT): weights are frozen by construction, so the
custom VJP only propagates dx = (g * scales) @ values^T - the scale folds
into the cotangent *before* the int8 contraction, which keeps the
transposed matmul scale-free too. Weight cotangents are symbolic zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _dequant_matmul_kernel(x_ref, v_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    w = v_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(x, w,
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def dequant_matmul_call(x2d, values, scales, *, interpret: bool,
                        block_m: int = 128, block_n: int = 128):
    """x2d: (M, K); values: (K, N) int8/fp8; scales: (1, N) or (N,) fp32."""
    M, K = x2d.shape
    Kw, N = values.shape
    if K != Kw:
        raise ValueError(f"contraction mismatch: x {x2d.shape} vs w {values.shape}")
    s2d = scales.reshape(1, N)
    bm, bn = min(block_m, M), min(block_n, N)
    grid = (_cdiv(M, bm), _cdiv(N, bn))
    return pl.pallas_call(
        _dequant_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2d.dtype),
        interpret=interpret,
    )(x2d, values, s2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dequant_matmul_tpu(x, values, scales, interpret: bool):
    """Fused dequant-matmul. x: (M, K); values: (K, N); scales: (1, N)|(N,)."""
    return dequant_matmul_call(x, values, scales, interpret=interpret)


def _dqmm_fwd(x, values, scales, interpret):
    return dequant_matmul_tpu(x, values, scales, interpret), (values, scales)


def _dqmm_bwd(interpret, res, g):
    values, scales = res
    g32 = g.astype(jnp.float32) * scales.reshape(1, -1).astype(jnp.float32)
    # the kernel emits x.dtype, so the incoming cotangent already carries it
    dx = (g32 @ values.astype(jnp.float32).T).astype(g.dtype)
    # frozen weights: cotangents are (symbolic) zeros - float0 for the int8
    # payload, a zero array for inexact (fp8) payloads and the scales
    if jnp.issubdtype(jnp.asarray(values).dtype, jnp.inexact):
        dv = jnp.zeros(values.shape, values.dtype)
    else:
        dv = np.zeros(values.shape, jax.dtypes.float0)
    return dx, dv, jnp.zeros(scales.shape, scales.dtype)


dequant_matmul_tpu.defvjp(_dqmm_fwd, _dqmm_bwd)
