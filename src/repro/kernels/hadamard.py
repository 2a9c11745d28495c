"""Pallas TPU kernels for the Hadamard adapter (paper Eq. 5) and its fusion
with the residual add + following norm.

Why a kernel at all: the adapter is a pure VPU op between two MXU ops. Left
to XLA it costs one extra HBM round-trip of the (B,S,d) activation per
layer. Fused with the residual-add and the ffn_norm that always follows it,
the sequence costs exactly one read and two writes.

VMEM tiling: rows of the flattened (B*S, d) activation are blocked by
`block_rows`; d stays whole inside a block (norms are row-wise). For
d = 8192 and block_rows = 256 the working set is ~8 MB fp32 - within the
~16 MB v5e VMEM budget with double buffering at bf16.

The plain affine has a full Pallas VJP (dx elementwise; dw/db fp32
reductions accumulated across the sequential row-grid). The fused variant's
backward composes the same kernels with the norm VJP in jnp.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _block_rows(d: int, want: int = 256) -> int:
    # keep the fp32 working set of one block around ~4MB
    cap = max(8, (1 << 20) // max(d, 1) * 4)
    return int(min(want, cap))


def _rows_grid(n_rows: int, bm: int):
    return (n_rows + bm - 1) // bm


# ---------------------------------------------------------------------------
# Plain affine: y = x*w + b
# ---------------------------------------------------------------------------


def _affine_fwd_kernel(x_ref, w_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (x * w_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _affine_bwd_kernel(g_ref, x_ref, w_ref, dx_ref, dw_ref, db_ref, *,
                       n_rows: int, bm: int):
    i = pl.program_id(0)
    g = g_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    dx_ref[...] = (g * w_ref[...].astype(jnp.float32)).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    # mask padding rows of the final partial block out of the reductions
    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    g = jnp.where(row < n_rows, g, 0.0)
    gx = jnp.where(row < n_rows, g * x, 0.0)
    dw_ref[...] += jnp.sum(gx, axis=0)
    db_ref[...] += jnp.sum(g, axis=0)


def _affine_call(x2d, w, b, *, interpret: bool):
    n, d = x2d.shape
    bm = _block_rows(d)
    grid = (_rows_grid(n, bm),)
    return pl.pallas_call(
        _affine_fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        interpret=interpret,
    )(x2d, w, b)


def _affine_bwd_call(g2d, x2d, w, *, interpret: bool):
    n, d = g2d.shape
    bm = _block_rows(d)
    grid = (_rows_grid(n, bm),)
    return pl.pallas_call(
        functools.partial(_affine_bwd_kernel, n_rows=n, bm=bm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),  # accumulated across grid
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), g2d.dtype),
            jax.ShapeDtypeStruct((d,), jnp.float32),
            jax.ShapeDtypeStruct((d,), jnp.float32),
        ],
        interpret=interpret,
    )(g2d, x2d, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def hadamard_affine(x, w, b, interpret: bool):
    """y = x * w + b over the trailing dim. x: (..., d); w,b: (d,)."""
    shape = x.shape
    y = _affine_call(x.reshape(-1, shape[-1]), w, b, interpret=interpret)
    return y.reshape(shape)


def _had_fwd(x, w, b, interpret):
    return hadamard_affine(x, w, b, interpret), (x, w)


def _had_bwd(interpret, res, g):
    x, w = res
    shape = x.shape
    dx, dw, db = _affine_bwd_call(
        g.reshape(-1, shape[-1]), x.reshape(-1, shape[-1]), w,
        interpret=interpret)
    return dx.reshape(shape), dw.astype(w.dtype), db.astype(w.dtype)


hadamard_affine.defvjp(_had_fwd, _had_bwd)


# ---------------------------------------------------------------------------
# Fused: x_new = x*w + b + res ; h = Norm(x_new)*scale (+bias)
# ---------------------------------------------------------------------------


def _fused_kernel(x_ref, res_ref, w_ref, b_ref, scale_ref, xn_ref, h_ref,
                  *, eps: float, layernorm: bool, bias_ref=None):
    x = x_ref[...].astype(jnp.float32)
    r = res_ref[...].astype(jnp.float32)
    xn = x * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32) + r
    xn_ref[...] = xn.astype(xn_ref.dtype)
    if layernorm:
        mu = jnp.mean(xn, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xn - mu), axis=-1, keepdims=True)
        h = (xn - mu) * jax.lax.rsqrt(var + eps)
        h = h * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xn), axis=-1, keepdims=True)
        h = xn * jax.lax.rsqrt(ms + eps) * scale_ref[...].astype(jnp.float32)
    h_ref[...] = h.astype(h_ref.dtype)


def _fused_call(x, res, w, b, scale, bias, eps: float, interpret: bool):
    """The forward pallas_call. Returns (x_new, h); bias=None -> RMSNorm."""
    shape = x.shape
    d = shape[-1]
    x2, r2 = x.reshape(-1, d), res.reshape(-1, d)
    n = x2.shape[0]
    bm = _block_rows(d)
    grid = (_rows_grid(n, bm),)
    layernorm = bias is not None

    vec = pl.BlockSpec((d,), lambda i: (0,))
    row = pl.BlockSpec((bm, d), lambda i: (i, 0))
    in_specs = [row, row, vec, vec, vec]
    args = [x2, r2, w, b, scale]
    if layernorm:
        in_specs.append(vec)
        args.append(bias)
        # reorder: bias_ref comes in positionally after the outputs otherwise;
        # wrap to place it correctly.
        def kernel(x_ref, res_ref, w_ref, b_ref, scale_ref, bias_ref, xn_ref, h_ref):
            _fused_kernel(x_ref, res_ref, w_ref, b_ref, scale_ref, xn_ref,
                          h_ref, eps=eps, layernorm=True, bias_ref=bias_ref)
    else:
        def kernel(x_ref, res_ref, w_ref, b_ref, scale_ref, xn_ref, h_ref):
            _fused_kernel(x_ref, res_ref, w_ref, b_ref, scale_ref, xn_ref,
                          h_ref, eps=eps, layernorm=False)

    xn, h = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((n, d), x.dtype),
        ],
        interpret=interpret,
    )(*args)
    return xn.reshape(shape), h.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _fused(x, res, w, b, scale, bias, eps: float, interpret: bool):
    return _fused_call(x, res, w, b, scale, bias, eps, interpret)


def _fused_fwd(x, res, w, b, scale, bias, eps, interpret):
    xn, h = _fused_call(x, res, w, b, scale, bias, eps, interpret)
    # xn is an output anyway: the norm stats are recomputed from it in the
    # backward, so the residuals add only what the affine bwd kernel needs
    return (xn, h), (x, w, b, scale, bias, xn)


def _fused_bwd(eps, interpret, residuals, cts):
    """Backward: jnp norm-VJP (row-wise, fp32) feeding the same Pallas
    affine-backward kernel the plain adapter uses for dx/dw/db.

      xn = x*w + b + res        h = Norm(xn)*scale (+bias)
      gt = g_xn + dNorm^T(g_h)  -> dx = gt*w, dres = gt,
                                   dw = sum(gt*x), db = sum(gt)
    """
    x, w, b, scale, bias, xn = residuals
    g_xn, g_h = cts
    shape = x.shape
    d = shape[-1]
    xn32 = xn.reshape(-1, d).astype(jnp.float32)
    gh32 = g_h.reshape(-1, d).astype(jnp.float32)
    g = gh32 * scale.astype(jnp.float32)
    if bias is not None:  # LayerNorm
        mu = jnp.mean(xn32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xn32 - mu), axis=-1, keepdims=True)
        r = jax.lax.rsqrt(var + eps)
        xhat = (xn32 - mu) * r
        dxn = r * (g - jnp.mean(g, axis=-1, keepdims=True)
                   - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
        dscale = jnp.sum(gh32 * xhat, axis=0)
        dbias = jnp.sum(gh32, axis=0).astype(bias.dtype)
    else:  # RMSNorm
        ms = jnp.mean(jnp.square(xn32), axis=-1, keepdims=True)
        r = jax.lax.rsqrt(ms + eps)
        dxn = r * g - xn32 * (r ** 3) * jnp.mean(g * xn32, axis=-1,
                                                 keepdims=True)
        dscale = jnp.sum(gh32 * xn32 * r, axis=0)
        dbias = None
    gt = dxn + g_xn.reshape(-1, d).astype(jnp.float32)
    dx, dw, db = _affine_bwd_call(gt, x.reshape(-1, d), w,
                                  interpret=interpret)
    return (dx.reshape(shape).astype(x.dtype),
            gt.reshape(shape).astype(x.dtype),  # dres: residual add is id
            dw.astype(w.dtype), db.astype(b.dtype),
            dscale.astype(scale.dtype), dbias)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_adapter_residual_norm(x, res, w, b, scale, *, eps: float = 1e-6,
                                bias: Optional[jax.Array] = None,
                                interpret: bool):
    """Returns (x_new, h). x/res: (..., d); w/b/scale[/bias]: (d,).

    Differentiable: the VJP composes the Pallas affine-backward kernel
    (dx/dw/db with fp32 cross-row reductions) with the LayerNorm/RMSNorm
    backward in jnp, exactly as the module docstring promises."""
    return _fused(x, res, w, b, scale, bias, eps, interpret)
