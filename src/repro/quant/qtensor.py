"""QTensor: the quantized-weight pytree leaf, and the symmetric
quantization primitives shared by serve-side weight quant and the
train-side error-feedback gradient compressor.

A QTensor packs `values` (int8 or fp8-e4m3) together with fp32 `scales`. Per-channel quantization of a matmul
weight (..., d_in, d_out) keeps one scale per *output* channel - scales
have shape (..., 1, d_out) - so the contraction dim stays scale-free and a
fused dequant-matmul kernel can fold the scale into the accumulator
epilogue. The collapsed contraction dim is also what makes the scale tree
trivially shardable: under tensor parallelism the values shard exactly
like the fp32 weight would, and `fit_spec` drops the 'model' entry from
the size-1 scale dim, leaving scales replicated along the sharded
contraction axis (see dist/sharding.py).

QTensor registers as a pytree-with-keys node, so the whole framework
treats a quantized tree like any other param tree: jit closes over it,
`lax.scan` slices the stacked (L, d_in, d_out) leaves layer by layer,
sharding/path machinery sees `<leaf>/values` and `<leaf>/scales` paths,
and the checkpoint store serializes it dtype-faithfully (int8 on disk,
restored cold without an fp32 detour).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# storage dtype and largest finite value per mode. Compute widens to fp32
# either way, so fp8 here is a storage format, not MXU fp8.
_QMAX = {"int8": 127.0, "fp8": 448.0}  # e4m3fn has no inf encoding
_DTYPE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}

QUANT_MODES = tuple(sorted(_QMAX))


def _storage_dtype(mode: str):
    if mode not in _DTYPE:
        raise ValueError(f"unknown quantization mode {mode!r} "
                         f"(known: {QUANT_MODES})")
    return _DTYPE[mode]


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class QTensor:
    """values: int8/fp8 payload; scales: fp32, broadcastable to values.

    Kept deliberately permissive: pytree transforms (scan slicing, shard
    spec trees, device_put targets) rebuild QTensors whose fields are not
    arrays, so the constructor must not validate.
    """

    values: Any
    scales: Any

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten_with_keys(self):
        return (
            (jax.tree_util.GetAttrKey("values"), self.values),
            (jax.tree_util.GetAttrKey("scales"), self.scales),
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    # -- conveniences -------------------------------------------------------
    @property
    def shape(self):
        return self.values.shape

    @property
    def ndim(self):
        return len(self.values.shape)

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                   for a in (self.values, self.scales))

    def dequantize(self, dtype=jnp.float32):
        w = (jnp.asarray(self.values).astype(jnp.float32)
             * jnp.asarray(self.scales).astype(jnp.float32))
        return w.astype(dtype)


def is_qtensor(v) -> bool:
    return isinstance(v, QTensor)


# ---------------------------------------------------------------------------
# Quantize / dequantize
# ---------------------------------------------------------------------------


def quantize(x, mode: str = "int8", *, axis: Optional[int] = -2,
             clip: float = 1.0) -> QTensor:
    """Symmetric quantization of `x` to a QTensor.

    axis=-2 (default): per-channel over the contraction dim of a matmul
    weight (..., d_in, d_out) -> scales (..., 1, d_out), one scale per
    output channel. axis=None: one per-tensor scale (the EF gradient
    compressor's layout). `clip` < 1 shrinks the clipping range (values
    saturate at the grid edge), trading outlier fidelity for resolution -
    the calibration pass picks it per leaf.
    """
    dtype = _storage_dtype(mode)
    qmax = _QMAX[mode]
    x32 = jnp.asarray(x).astype(jnp.float32)
    if axis is None:
        absmax = jnp.max(jnp.abs(x32)).reshape((1,) * x32.ndim)
    else:
        absmax = jnp.max(jnp.abs(x32), axis=axis, keepdims=True)
    scale = clip * absmax / qmax
    scale = jnp.where(scale > 0, scale, 1.0)  # all-zero channel: identity
    q = jnp.clip(x32 / scale, -qmax, qmax)
    if mode == "int8":
        q = jnp.round(q)
    return QTensor(q.astype(dtype), scale.astype(jnp.float32))


def fake_quantize(x, mode: str = "int8", *, axis: Optional[int] = None,
                  clip: float = 1.0):
    """quantize -> dequantize in one step (fp32 out): the shared primitive
    behind the train-side EF gradient compressor (optim/compression.py)."""
    return quantize(x, mode, axis=axis, clip=clip).dequantize(jnp.float32)


def quantization_error(x, qt: QTensor) -> jax.Array:
    """Mean-squared dequantization error (fp32 scalar)."""
    d = jnp.asarray(x).astype(jnp.float32) - qt.dequantize(jnp.float32)
    return jnp.mean(jnp.square(d))


# ---------------------------------------------------------------------------
# The matmul entry point every projection in models/ goes through
# ---------------------------------------------------------------------------


def qdense(x, w, dtype=None, tag: Optional[str] = None, impl: str = "auto"):
    """`x @ w` where `w` is a plain array OR a QTensor.

    Plain arrays take the exact pre-quant path (`x @ w.astype(dtype)`),
    optionally feeding the activation-statistics collector when a
    calibration pass is active (see calibrate.py - `tag` names the call
    site). QTensor weights dispatch to the fused dequant-matmul kernel:
    int8 weights stream from HBM and are dequantized into the matmul
    epilogue, never materializing an fp32 copy of the weight.
    """
    if isinstance(w, QTensor):
        from repro.kernels import ops  # deferred: keep import graph acyclic

        if w.ndim != 2:
            raise ValueError(
                f"qdense expects a 2D QTensor (got {w.shape}); stacked "
                "group leaves are sliced to 2D by the layer scan")
        shape = x.shape
        y = ops.dequant_matmul(x.reshape(-1, shape[-1]), w.values, w.scales,
                               impl=impl)
        return y.reshape(shape[:-1] + (w.shape[-1],))
    # deferred import: calibrate's driver imports models, which imports us
    from repro.quant.calibrate import collecting, observe

    if tag is not None and collecting():
        observe(tag, x)
    return x @ w.astype(x.dtype if dtype is None else dtype)


# ---------------------------------------------------------------------------
# Tree-level quantization (the frozen backbone)
# ---------------------------------------------------------------------------

# Which leaves a backbone quantization touches: the dense/attention
# projections - the MXU-bound matmuls that dominate weight bytes. Embedding
# tables (gather path), norms, biases, heads (pooler/classifier), MoE
# expert stacks (einsum path) and every adapter leaf stay in their
# original dtype; for Hadamard PEFT that is exactly the trunk-is-frozen
# invariant: the KB-sized fp32 adapter keeps training/serving on top of a
# once-quantized base.
#
# One table drives both the allowlist and the calibration-tag map: each
# entry is (path regex, match -> qdense call-site tag), so a projection
# added here is automatically both quantized and calibrated.
_QUANT_TABLE = (
    (r"/(attn|cross)/(wq|wk|wv|wo)$", lambda m: f"attn/{m.group(2)}"),
    (r"/mlp/(wi|wg|wo)$", lambda m: f"mlp/{m.group(1)}"),
    (r"(^|/)lm_head/kernel$", lambda m: "lm_head"),
    (r"(^|/)vlm_proj/kernel$", lambda m: "vlm_proj"),
)

QUANT_PATTERNS = tuple(p for p, _ in _QUANT_TABLE)
_QUANT_RES = tuple(re.compile(p) for p in QUANT_PATTERNS)
_TAG_RES = tuple((re.compile(p), fmt) for p, fmt in _QUANT_TABLE)


def quantizable(path: str) -> bool:
    return any(r.search(path) for r in _QUANT_RES)


def tag_of(path: str) -> Optional[str]:
    for rx, fmt in _TAG_RES:
        m = rx.search(path)
        if m:
            return fmt(m)
    return None


_CLIP_GRID = (1.0, 0.95, 0.9, 0.85, 0.8, 0.7)


def _best_clip(leaf, mode: str, act_sq) -> float:
    """Activation-weighted clipping search (one-off, at quantization time):
    pick the clip ratio minimizing sum_k m_k * (W - deq(Q(W)))^2_k, where
    m is the calibration pass's per-input-channel activation second moment
    - channels the data actually drives are the ones whose rounding error
    is worth spending grid resolution on."""
    w32 = jnp.asarray(leaf).astype(jnp.float32)
    m = jnp.asarray(act_sq, jnp.float32)
    if m.shape != (w32.shape[-2],):  # stats from a different width: skip
        return 1.0
    weights = m.reshape((1,) * (w32.ndim - 2) + (-1, 1))
    best, best_err = 1.0, None
    for c in _CLIP_GRID:
        deq = quantize(w32, mode, clip=c).dequantize(jnp.float32)
        err = float(jnp.sum(weights * jnp.square(w32 - deq)))
        if best_err is None or err < best_err:
            best, best_err = c, err
    return best


def quantize_tree(params, mode: str = "int8", *, stats=None,
                  patterns=None):
    """Quantize every backbone matmul leaf of a param(-shaped) tree.

    Leaves whose path matches `patterns` (default: QUANT_PATTERNS) and that
    are floating arrays of ndim >= 2 become QTensors with per-output-
    channel scales; everything else passes through untouched - including
    None placeholders, so a PEFT-partitioned `frozen` tree quantizes
    directly (QPEFT: the trainable adapter subtree is None here and stays
    fp32 in its own tree). `stats` is the calibration pass's output
    ({tag: per-input-channel activation second moment}); when given, each
    leaf gets an activation-weighted clipping search instead of plain
    absmax scaling. Idempotent: QTensor leaves pass through whole (the
    tree is flattened with QTensor as a leaf, so no pattern - however
    broad - can ever re-quantize a scales array).
    """
    from repro.common import tree as tu

    regexes = (_QUANT_RES if patterns is None
               else tuple(re.compile(p) for p in patterns))

    def one(path, leaf):
        if leaf is None or isinstance(leaf, QTensor):
            return leaf
        if not any(r.search(path) for r in regexes):
            return leaf
        if getattr(leaf, "ndim", 0) < 2 or not jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating):
            return leaf
        clip = 1.0
        if stats:
            tag = tag_of(path)
            if tag in stats:
                clip = _best_clip(leaf, mode, stats[tag])
        return quantize(leaf, mode, clip=clip)

    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda v: v is None or isinstance(v, QTensor))
    return jax.tree_util.tree_unflatten(
        treedef, [one(tu.path_str(p), leaf) for p, leaf in leaves])


def dequantize_tree(tree, dtype=jnp.float32):
    """Inverse of quantize_tree: QTensor leaves -> dense arrays."""
    return jax.tree.map(
        lambda v: v.dequantize(dtype) if isinstance(v, QTensor) else v,
        tree, is_leaf=lambda v: v is None or isinstance(v, QTensor))


def quant_summary(tree) -> dict:
    """Byte accounting for the README/bench memory table.

    quantized_bytes counts QTensor payload+scales; dense_bytes_fp32 is
    what the same leaves cost at fp32. ratio is the compression of the
    quantized set; total_bytes prices the whole tree as it stands.
    """
    from repro.common import tree as tu

    quantized = dense_fp32 = n_q = 0
    for leaf in jax.tree.leaves(
            tree, is_leaf=lambda v: v is None or isinstance(v, QTensor)):
        if isinstance(leaf, QTensor):
            n_q += 1
            quantized += leaf.nbytes
            dense_fp32 += int(np.prod(leaf.shape)) * 4
    return {
        "n_quantized_leaves": n_q,
        "quantized_bytes": quantized,
        "dense_bytes_fp32": dense_fp32,
        "ratio": dense_fp32 / quantized if quantized else 1.0,
        "total_bytes": tu.tree_bytes(tree),
    }
