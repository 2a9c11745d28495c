"""Path-pattern sharding rule engine: param/cache tree paths -> PartitionSpecs.

One rule table covers every assigned architecture because the layer
program gives every family the same path vocabulary (stacked group leaves
under blocks/gN/slotM/...). The policy:

  * tensor parallelism ('model' axis): attention/FFN matmuls are
    column-parallel on their output dim and row-parallel on their input
    dim (Megatron layout: one all-reduce per sublayer pair); embeddings
    and lm_head shard the vocab dim.
  * expert parallelism: MoE expert-stacked weights (..., E, d, f) put the
    expert dim on 'model' - the (G, E, cap, d) dispatch buffer crossing
    from dp-sharded groups to model-sharded experts is the all-to-all
    (see models/moe.py). A held share (MoECfg.held) stacks only its
    experts, under the same rule; the router keeps every expert's
    column and stays replicated.
  * latent attention (models/mla.py): wq and the latent's up-projection
    wkv_b are column-parallel (heads on 'model'), wo row-parallel; the
    latent's down-projection wkv_a and its norm are replicated, and so
    is the latent cache: every device reads the whole latent for its
    own heads.
  * FSDP (shard_profile='tp_fsdp'): large leaves additionally shard one
    free dim over 'data' (weight-gather on use, Zero-3 style).
  * everything else - norms, biases, the paper's Hadamard adapters - is
    replicated: adapter leaves are KB-sized, and replication is what lets
    multi-task serving gather per-request adapters without collectives.

Every produced entry is validated against the leaf shape (`fit_spec`):
an axis that does not evenly divide its dim is dropped, optionally
promoting 'model' to the largest dim that does divide (whisper's 51865
vocab on a 16-way model axis promotes to the d_model dim).
"""
from __future__ import annotations

import re
from typing import List, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import tree as tu
from repro.dist.api import _axis_size, dp_axes, mesh_axis_sizes

# Leaves smaller than this stay replicated-on-drop (no model promotion)
# and never get FSDP treatment: collectives on KB-sized leaves cost more
# than they save.
_PROMOTE_MIN = 1 << 20
_FSDP_MIN = 1 << 20

# (regex on the tree path) -> placement template over the TRAILING dims:
#   'col'    : output dim (last) on 'model'     - column-parallel matmul
#   'row'    : input dim (last-1) on 'model'    - row-parallel matmul
#   'embed'  : first-of-last-two dims on 'model' (vocab/position tables,
#              with promotion to the other dim when indivisible)
#   'expert' : expert dim (last-2) on 'model'   - expert parallelism
# Anything unmatched is replicated.
_RULES: Tuple[Tuple[re.Pattern, str], ...] = tuple(
    (re.compile(pat), kind)
    for pat, kind in (
        (r"(^|/)(embed|pos_embed|type_embed|enc_pos_embed)/table$", "embed"),
        (r"(^|/)lm_head/kernel$", "col"),
        (r"(^|/)vlm_proj/kernel$", "col"),
        (r"/(attn|cross)/(wq|wk|wv)$", "col"),
        (r"/attn/wkv_b$", "col"),
        (r"/(attn|cross)/wo$", "row"),
        (r"/mlp/(wi|wg)$", "col"),
        (r"/mlp/wo$", "row"),
        (r"/moe/(wi|wg|wo)$", "expert"),
        (r"/moe/shared_w[ig]$", "col"),
        (r"/moe/shared_wo$", "row"),
        (r"/rec/(in_x|in_y|gate_a|gate_x)$", "col"),
        (r"/rec/out$", "row"),
        (r"/rwkv_tm/(wr|wk|wv|wg|lora1|wA)$", "col"),
        (r"/rwkv_tm/(wo|wB)$", "row"),
        (r"/rwkv_cm/(ck|cr)$", "col"),
        (r"/rwkv_cm/cv$", "row"),
    )
)

_MODEL_DIM_FROM_END = {"col": 1, "row": 2, "embed": 2, "expert": 3}

# Hadamard adapter leaves - including their (L, T, d) bank-stacked form
# and the single-row (L, 1, d) w leaves of a shared-w bank
# (repro.sparse) - are pinned replicated by construction, not merely by
# falling through the rule table: hot-swap row inserts are host-driven
# donated scatters on the task axis, and the per-request bank gather
# inside the decode tick is collective-free only while every device holds
# every row. Sparse-serving layer masks/gates ((L,)/(L, T) bools, KBs)
# are replicated for the same reason: the masked kernel reads every
# request's row gate every tick (`adapter_gate_shardings` below).
_ADAPTER_RE = re.compile(r"/adapter/")

# Quantized leaves (repro.quant.QTensor) flatten to `<leaf>/values` and
# `<leaf>/scales` paths. Both are matched against the base leaf's rule:
# values shard exactly like the fp32 weight would; scales reuse the same
# placement template, and because the contraction dim is collapsed to 1 in
# the scale shape, `fit_spec` drops the 'model' entry there - i.e. scales
# of row-parallel weights come out replicated along the sharded
# contraction axis, while scales of column-parallel weights shard with
# their output channels. No special cases: the fit_spec fallback is the
# mechanism.
_QT_LEAF_RE = re.compile(r"/(values|scales)$")


def fit_spec(entries: Sequence, shape: Sequence[int], mesh,
             promote_model: bool = False) -> List:
    """Validate spec entries against a shape: drop any axis whose size does
    not evenly divide its dim. With `promote_model`, a dropped (or absent)
    'model' entry is re-placed on the largest unsharded dim it divides."""
    sizes = mesh_axis_sizes(mesh)
    out: List = []
    for dim, e in zip(shape, entries):
        if e is None or dim % _axis_size(e, sizes) != 0:
            out.append(None)
        else:
            out.append(e)
    while len(out) < len(shape):
        out.append(None)

    if promote_model and "model" not in out:
        m = sizes.get("model", 1)
        candidates = [
            i for i, dim in enumerate(shape)
            if out[i] is None and m > 1 and dim % m == 0 and dim >= m
        ]
        if candidates:
            out[max(candidates, key=lambda i: shape[i])] = "model"
    return out


def _match_rule(path: str) -> Optional[str]:
    for rx, kind in _RULES:
        if rx.search(path):
            return kind
    return None


def param_spec(path: str, shape: Sequence[int], cfg, mesh) -> P:
    """PartitionSpec for one param leaf. Stacked group leaves carry a
    leading `repeats` dim which is never sharded (it is the scan axis).
    QTensor component paths (`.../values`, `.../scales`) resolve against
    their base leaf's rule (see _QT_LEAF_RE note)."""
    qt = _QT_LEAF_RE.search(path)
    if qt is not None:
        path = path[: qt.start()]
    if _ADAPTER_RE.search(path):
        return P()  # bank rows stay replicated (see _ADAPTER_RE note)
    kind = _match_rule(path)
    ndim = len(shape)
    if kind is None or ndim < 2:
        return P()  # replicated (norms, biases, routers, scalars)

    offset = _MODEL_DIM_FROM_END[kind]
    if ndim < offset:
        return P()
    entries: List = [None] * ndim
    entries[ndim - offset] = "model"

    numel = int(np.prod(shape))
    entries = fit_spec(entries, shape, mesh,
                       promote_model=(kind == "embed" and numel >= _PROMOTE_MIN))

    if cfg.shard_profile == "tp_fsdp" and numel >= _FSDP_MIN:
        dsize = mesh_axis_sizes(mesh).get("data", 1)
        candidates = [
            i for i, dim in enumerate(shape)
            if entries[i] is None and dsize > 1 and dim % dsize == 0 and dim >= dsize
        ]
        if candidates:
            entries[max(candidates, key=lambda i: shape[i])] = "data"

    return P(*entries)


def batch_spec(mesh, ndim: int, shape: Sequence[int]) -> P:
    """Batch-dim sharding over the data-parallel axes (dropped when the
    leading dim is indivisible, e.g. global batch 1 at 500k context)."""
    dp = dp_axes(mesh)
    entry = dp[0] if len(dp) == 1 else dp
    n = _axis_size(entry, mesh_axis_sizes(mesh))
    entries: List = [None] * ndim
    if ndim >= 1 and shape[0] % n == 0 and shape[0] >= n:
        entries[0] = entry
    return P(*entries)


_CACHE_KV_RE = re.compile(r"/(attn|cross)/c?[kv]$")


def cache_spec(path: str, shape: Sequence[int], cfg, mesh) -> P:
    """PartitionSpec for one decode-cache leaf.

    Stacked caches are (repeats, batch, ...): the batch dim goes on the
    dp axes. Attention K/V caches (repeats, batch, S, KH, Dh) also get
    'model' on the kv-head dim, falling back to the head_dim when there
    are too few kv heads (MQA) - either way the decode gather stays local.
    """
    sizes = mesh_axis_sizes(mesh)
    ndim = len(shape)
    entries: List = [None] * ndim

    dp = dp_axes(mesh)
    dp_entry = dp[0] if len(dp) == 1 else dp
    n = _axis_size(dp_entry, sizes)
    if ndim >= 2 and shape[1] % n == 0 and shape[1] >= n:
        entries[1] = dp_entry

    if _CACHE_KV_RE.search(path) and ndim >= 5:
        m = sizes.get("model", 1)
        if m > 1:
            if shape[-2] % m == 0 and shape[-2] >= m:
                entries[-2] = "model"  # shard kv heads
            elif shape[-1] % m == 0 and shape[-1] >= m:
                entries[-1] = "model"  # MQA fallback: shard head_dim
    return P(*entries)


# ---------------------------------------------------------------------------
# Tree-level shardings (jit in_shardings / host device_put targets)
# ---------------------------------------------------------------------------


def params_shardings(tree, cfg, mesh):
    """Map a param(-shaped) tree to NamedShardings via `param_spec`.

    Accepts arrays or ShapeDtypeStructs; works on partitioned trees
    (None leaves pass through as pytree nodes untouched)."""
    def one(path, leaf):
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, param_spec(path, shape, cfg, mesh))

    return tu.map_with_path(one, tree)


def cache_shardings(caches, cfg, mesh):
    """Map a decode-cache tree to NamedShardings via `cache_spec`."""
    def one(path, leaf):
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, cache_spec(path, shape, cfg, mesh))

    return tu.map_with_path(one, caches)


def opt_state_shardings(opt_state, cfg, mesh):
    """NamedShardings for an AdamW state tree (repro.optim.qstate).

    Moment trees mirror the trainable tree's paths under `m/`, `v/` (and
    `m_err/`/`v_err/` residual) prefixes, so the rule table resolves each
    moment leaf against its parameter's own pattern - a moment (or its
    QTensor `values`) shards exactly like the leaf it tracks, which is
    what keeps the optimizer update collective-free under TP/FSDP. Scale
    leaves reuse the same template and `fit_spec` drops any entry landing
    on the collapsed size-1 block dim: scales of column-parallel weights
    (blocked along the sharded output dim) come out replicated, scales of
    row-parallel weights co-shard with their rows. The `count` scalar is
    replicated.
    """
    return params_shardings(opt_state, cfg, mesh)


# ---------------------------------------------------------------------------
# Slot-pool caches (continuous-batching scheduler)
# ---------------------------------------------------------------------------


def slot_cache_spec(path: str, shape: Sequence[int], cfg, mesh) -> P:
    """PartitionSpec for one slot-pool cache leaf.

    The pool's slot dim (axis 1 of stacked (repeats, num_slots, ...)
    leaves) is NOT a data-parallel batch: slots are admitted and retired
    one at a time, out of order, by host-side scatters. Sharding it over
    the dp axes would turn every admission into a resharding collective
    and tie num_slots to the mesh shape, so it stays replicated. Model
    parallelism on the kv-head/head dims applies exactly as in
    `cache_spec` - the decode gather stays local. The speculative draft
    lane (serving/spec.py) keeps a second slot-cache pool under these
    same rules, so draft and target admissions shard identically.
    """
    entries = list(cache_spec(path, shape, cfg, mesh))
    while len(entries) < len(shape):
        entries.append(None)
    if len(entries) >= 2:
        entries[1] = None  # slot dim: replicated
    return P(*entries)


def slot_cache_shardings(caches, cfg, mesh):
    """Map a slot-pool cache tree to NamedShardings via `slot_cache_spec`."""
    def one(path, leaf):
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, slot_cache_spec(path, shape, cfg, mesh))

    return tu.map_with_path(one, caches)


# ---------------------------------------------------------------------------
# Paged block pools (serving/paged.py)
# ---------------------------------------------------------------------------


def paged_cache_spec(path: str, shape: Sequence[int], cfg, mesh) -> P:
    """PartitionSpec for one paged block-pool leaf.

    Pool leaves are (repeats, num_blocks, page, KH, Dh) - or their QTensor
    values/scales components. The block dim is the allocator's free list:
    blocks are handed out one at a time to arbitrary slots by host-side
    refcounting, so sharding it would turn every allocate-on-write into a
    resharding collective and couple pool capacity to the mesh shape; it
    stays replicated, like the slot dim of `slot_cache_spec`. Model
    parallelism goes on the kv-head dim (head_dim MQA fallback), so every
    device holds the full block table's worth of its head shard and the
    paged gather stays local. A latent leaf (`.../attn/latent`) has no
    heads to split and stays replicated.
    """
    qt = _QT_LEAF_RE.search(path)
    if qt is not None:
        path = path[: qt.start()]
    ndim = len(shape)
    entries: List = [None] * ndim
    if _CACHE_KV_RE.search(path) and ndim >= 5:
        m = mesh_axis_sizes(mesh).get("model", 1)
        if m > 1:
            if shape[-2] % m == 0 and shape[-2] >= m:
                entries[-2] = "model"  # shard kv heads
            elif shape[-1] % m == 0 and shape[-1] >= m:
                entries[-1] = "model"  # MQA fallback: shard head_dim
    return P(*entries)


def paged_cache_shardings(pool, cfg, mesh):
    """Map a paged block-pool tree to NamedShardings via `paged_cache_spec`."""
    def one(path, leaf):
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, paged_cache_spec(path, shape, cfg, mesh))

    return tu.map_with_path(one, pool)


# ---------------------------------------------------------------------------
# Adapter-bank rows (hot-swap serving, serving/registry.py)
# ---------------------------------------------------------------------------


def adapter_row_shardings(row, mesh):
    """NamedShardings for one adapter row about to be scattered into a live
    bank: fully replicated, matching the bank leaves it lands in (adapter
    paths in `param_spec`). Placing the KB-sized row everywhere up front
    keeps the donated in-place insert a local write on every device - no
    resharding collective inside the hot-swap path."""
    return tu.map_with_path(lambda p, l: NamedSharding(mesh, P()), row)


def adapter_gate_shardings(gates, mesh):
    """NamedShardings for sparse-serving gate/mask arrays ((T,) or (L, T)
    row gates consumed by the masked multitask kernel, see
    kernels/sparse.py): fully replicated - they are bytes-sized, read by
    every device every decode tick, and mutated by the same host-driven
    hot-swap path as the adapter rows they gate."""
    return tu.map_with_path(lambda p, l: NamedSharding(mesh, P()), gates)
