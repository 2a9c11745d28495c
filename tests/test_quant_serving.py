"""End-to-end quantized serving: token parity of int8 engines against
fp32 (exact on losslessly-quantizable trunks, bounded top-1 agreement on
arbitrary ones), the scheduler fuzz at int8 vs the fp32 oracle, the
no-retrace contract across adapter hot-swaps, and cold restore of
quantized checkpoints straight into a serving engine.
"""
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import tiny_cfg
from repro.common import tree as tu
from repro.core.hadamard import extract_delta, perturb_adapters
from repro.models import model as M
from repro.quant import is_qtensor, quant_summary, quantize_tree
from repro.quant.qtensor import quantizable
from repro.serving import ServingConfig, make_scheduler
from repro.serving.engine import MultiTaskEngine, ServeEngine
from repro.serving.scheduler import Request

KEY = jax.random.PRNGKey(0)


def _snap_to_grid(params):
    """Quantizable leaves -> exact power-of-two int8 grid points, so int8
    quantization is lossless and parity assertions can be bit-exact."""

    def snap(path, leaf):
        if not quantizable(path):
            return leaf
        rs = np.random.RandomState(
            np.frombuffer(path.encode()[-4:].rjust(4, b"\0"),
                          np.uint32)[0] % 2**31)
        v = rs.randint(-127, 128, size=leaf.shape).astype(np.float32)
        v[..., 0, :] = 127.0
        e = rs.randint(-8, -3, size=leaf.shape[:-2] + (1, leaf.shape[-1]))
        return jnp.asarray(v * (2.0 ** e).astype(np.float32))

    return tu.map_with_path(snap, params)


def test_quantized_engine_greedy_token_parity_exact():
    cfg = tiny_cfg()
    params = _snap_to_grid(M.init_params(KEY, cfg))
    toks = np.asarray(jax.random.randint(KEY, (4, 8), 0, 97))

    want = ServeEngine(cfg, params).generate(toks, 8)
    got = ServeEngine(cfg, params, quant="int8").generate(toks, 8)
    np.testing.assert_array_equal(got, want)


def test_quantized_engine_bounded_top1_agreement_unsnapped():
    """On an arbitrary (non-grid) trunk int8 cannot be exact, but greedy
    tokens on short prompts must overwhelmingly agree with fp32."""
    cfg = tiny_cfg()
    params = M.init_params(KEY, cfg)
    toks = np.asarray(jax.random.randint(KEY, (6, 8), 0, 97))
    want = ServeEngine(cfg, params).generate(toks, 6)
    got = ServeEngine(cfg, params, quant="int8").generate(toks, 6)
    assert (got == want).mean() >= 0.8


def test_quantized_engine_fold_then_quant():
    """--fold --quant composes: fold first (fp32 surgery on W_O), then
    quantize the folded weights; tokens match the folded fp32 engine."""
    cfg = tiny_cfg()
    params = _snap_to_grid(M.init_params(KEY, cfg))
    # folding scales W_O by the adapter w: keep it on-grid with w=1, b!=0
    toks = np.asarray(jax.random.randint(KEY, (3, 8), 0, 97))
    want = ServeEngine(cfg, params, fold=True).generate(toks, 6)
    got = ServeEngine(cfg, params, fold=True, quant="int8").generate(toks, 6)
    np.testing.assert_array_equal(got, want)


def test_fp8_engine_serves():
    cfg = tiny_cfg()
    params = M.init_params(KEY, cfg)
    toks = np.asarray(jax.random.randint(KEY, (2, 8), 0, 97))
    want = ServeEngine(cfg, params).generate(toks, 4)
    got = ServeEngine(cfg, params, quant="fp8").generate(toks, 4)
    assert got.shape == want.shape
    assert (got == want).mean() >= 0.5  # e4m3 is coarser than int8


# ---------------------------------------------------------------------------
# Scheduler fuzz at int8 against the fp32 oracle
# ---------------------------------------------------------------------------


_WORLD = {}


def _world():
    """Snapped backbone + 3 named adapters; fp32 static oracle + int8
    hot-swap engine (2-row bank), built once per session."""
    if not _WORLD:
        from repro.serving.registry import AdapterBank, AdapterRegistry

        cfg = tiny_cfg()
        base = _snap_to_grid(M.init_params(KEY, cfg))
        variants = [
            perturb_adapters(base, jax.random.fold_in(KEY, 70 + t), scale=0.2)
            for t in range(3)
        ]
        td = tempfile.mkdtemp()
        registry = AdapterRegistry(td)
        for t, v in enumerate(variants):
            registry.publish(f"task{t}", extract_delta(v))
        _WORLD.update(
            cfg=cfg,
            oracle=MultiTaskEngine(cfg, variants),
            hot=MultiTaskEngine(cfg, AdapterBank(cfg, base, 2, registry),
                                quant="int8"),
        )
    return _WORLD


@pytest.mark.parametrize("seed", [0, 1])
def test_scheduler_fuzz_int8_vs_fp32_oracle(seed):
    """Randomized traffic (staggered arrivals, random prompts/budgets/
    adapters, mid-stream EOS) through the int8 hot-swap engine must be
    token-exact against the lock-step fp32 oracle."""
    w = _world()
    rs = np.random.RandomState(400 + seed)
    n_req = 10

    reqs, wants = [], []
    for i in range(n_req):
        plen = int(rs.randint(2, 9))
        budget = int(rs.randint(1, 7))
        task = int(rs.randint(0, 3))
        prompt = rs.randint(0, 97, size=(plen,)).astype(np.int32)
        ref = np.asarray(w["oracle"].generate_for_tasks(
            prompt.reshape(1, -1), np.array([task]), budget))[0]
        eos = int(ref[rs.randint(0, budget)]) if rs.rand() < 0.3 else None
        if eos is not None:
            hit = np.flatnonzero(ref == eos)
            ref = ref[: hit[0] + 1]
        reqs.append((int(rs.randint(0, 8)), Request(
            prompt=prompt, max_new_tokens=budget, adapter=f"task{task}",
            eos_id=eos)))
        wants.append(ref)

    sched = make_scheduler(w["hot"],
                           ServingConfig(num_slots=3, max_len=16))
    ids = [None] * n_req
    t = 0
    while None in ids or sched.pending or sched.active:
        for i, (arr, r) in enumerate(reqs):
            if ids[i] is None and arr <= t:
                ids[i] = sched.submit(r)
        sched.step()
        t += 1
        assert t < 500, "episode failed to drain"

    for i, rid in enumerate(ids):
        c = sched.completions.pop(rid)
        np.testing.assert_array_equal(c.tokens, wants[i],
                                      err_msg=f"seed {seed} req {i}")


def test_quant_times_sparse_packed_rows_stay_fp32():
    """quant x sparse composition: packed sparse adapters served through
    an int8 engine decode token-exactly (pruned layers as identity), and
    the bank's unpacked rows stay fp32 - quantization never touches
    adapter leaves, and PackedRows itself refuses non-fp32 rows."""
    from repro.serving.registry import AdapterBank, AdapterRegistry
    from repro.sparse import (apply_layer_mask, depth_mask, is_packed,
                              prune_delta)
    from repro.sparse.prune import PackedRows

    cfg = tiny_cfg()
    base = _snap_to_grid(M.init_params(KEY, cfg))
    mask = depth_mask(cfg, 1)
    variants = [
        apply_layer_mask(
            perturb_adapters(base, jax.random.fold_in(KEY, 90 + t),
                             scale=0.2), cfg, mask)
        for t in range(2)
    ]
    td = tempfile.mkdtemp()
    registry = AdapterRegistry(td)
    for t, v in enumerate(variants):
        registry.publish(f"task{t}", prune_delta(extract_delta(v), cfg, mask))

    oracle = MultiTaskEngine(cfg, variants)  # fp32, dense
    hot = MultiTaskEngine(cfg, AdapterBank(cfg, base, 2, registry),
                          quant="int8")
    toks = np.asarray(jax.random.randint(KEY, (2, 8), 0, 97))
    want = oracle.generate_for_tasks(toks, np.array([0, 1]), 6)
    got = hot.generate_for_adapters(toks, ["task0", "task1"], 6)
    np.testing.assert_array_equal(got, want)

    # every live bank adapter leaf is a plain fp32 array - no QTensor, no
    # int8 payload anywhere near a tenant's rows
    for path, leaf in tu.flatten_with_paths(hot.bank):
        if "/adapter/" not in path:
            continue
        assert not is_qtensor(leaf), path
        assert np.asarray(leaf).dtype == np.float32, path
    # and the packed form itself rejects quantized rows at construction
    with pytest.raises(ValueError, match="fp32"):
        PackedRows(np.array([True]), np.zeros((1, 4), np.int8), 0.0)
    # registry still holds the packed (fp32-rows) form on disk
    delta, _ = registry.load("task0")
    packed = [v for p, v in tu.flatten_with_paths(delta) if is_packed(v)]
    assert packed and all(v.rows.dtype == np.float32 for v in packed)


def test_quant_adds_no_retraces_across_swaps():
    """Hot-swapping adapters on a quantized engine must not retrace the
    decode tick: the QTensor leaves are jit constants-by-argument exactly
    like fp32 leaves, and row inserts only touch fp32 adapter leaves."""
    w = _world()
    hot = w["hot"]
    # the fuzz episodes above already churned the 2-row bank across 3
    # adapters (evictions + reloads); the compiled tick count must be flat
    assert hot.trace_counts["decode"] == 1, hot.trace_counts
    bank = hot.adapter_bank
    assert bank.stats()["loads"] >= 3  # the bank really did swap
    for name in list(bank.resident):
        assert bank.pins(name) == 0, name


# ---------------------------------------------------------------------------
# Quantized checkpoints: quantize once, restore cold in int8
# ---------------------------------------------------------------------------


def test_quantized_checkpoint_cold_restore_serves():
    from repro.checkpoint.manager import CheckpointManager

    cfg = tiny_cfg()
    params = _snap_to_grid(M.init_params(KEY, cfg))
    qparams = quantize_tree(params)
    toks = np.asarray(jax.random.randint(KEY, (3, 8), 0, 97))
    want = ServeEngine(cfg, params).generate(toks, 6)

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(0, qparams, filename="base_int8.ckpt")
        restored, meta = mgr.restore(filename="base_int8.ckpt")

    # cold restore: the loaded tree carries int8 QTensor leaves directly -
    # no fp32 detour anywhere between disk and the engine
    qleaves = [v for v in jax.tree.leaves(
        restored, is_leaf=lambda v: v is None or is_qtensor(v))
        if is_qtensor(v)]
    assert qleaves and all(
        np.asarray(q.values).dtype == np.int8 for q in qleaves)
    assert quant_summary(restored)["n_quantized_leaves"] == \
        quant_summary(qparams)["n_quantized_leaves"]

    # quant=None: the engine must NOT re-quantize; it serves the restored
    # QTensors as-is, token-identical to fp32
    got = ServeEngine(cfg, restored).generate(toks, 6)
    np.testing.assert_array_equal(got, want)


def test_quantized_checkpoint_dtype_faithful_bytes():
    """On-disk faithfulness: saving a quantized tree stores the int8
    payload (and fp32 scales), not a widened copy."""
    import os

    from repro.checkpoint.store import load_tree, save_tree

    rs = np.random.RandomState(0)
    w = rs.randn(64, 64).astype(np.float32)
    qt = quantize_tree({"mlp": {"wi": jnp.asarray(w)}},
                       patterns=(r"(^|/)mlp/wi$",))
    assert is_qtensor(qt["mlp"]["wi"])
    with tempfile.TemporaryDirectory() as d:
        pq = os.path.join(d, "q.ckpt")
        pf = os.path.join(d, "f.ckpt")
        save_tree(pq, qt, compress=False)
        save_tree(pf, {"mlp": {"wi": jnp.asarray(w)}}, compress=False)
        assert os.path.getsize(pq) < os.path.getsize(pf) / 2
        back, _ = load_tree(pq)
    assert is_qtensor(back["mlp"]["wi"])
    np.testing.assert_array_equal(
        np.asarray(back["mlp"]["wi"].values),
        np.asarray(qt["mlp"]["wi"].values))
