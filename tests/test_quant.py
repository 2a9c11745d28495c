"""repro.quant correctness: QTensor roundtrip bounds (property-tested),
the fused dequant-matmul kernel vs the fp32 oracle on non-block-aligned
shapes, tree quantization's allowlist/idempotence, calibration statistics,
sharding specs for values/scales, the shared-primitive contract with the
EF gradient compressor, and QPEFT gradient flow.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from conftest import tiny_cfg
from repro.common import tree as tu
from repro.common.types import OptimCfg
from repro.kernels import ops
from repro.models import model as M
from repro.quant import (
    QTensor,
    calibrate,
    dequantize_tree,
    fake_quantize,
    is_qtensor,
    quant_summary,
    quantize,
    quantize_tree,
)
from repro.quant.qtensor import quantizable, tag_of

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# QTensor roundtrip
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(rows=st.integers(1, 40), cols=st.integers(1, 40),
       scale_pow=st.integers(-6, 6), seed=st.integers(0, 2**16))
def test_int8_roundtrip_error_bound(rows, cols, scale_pow, seed):
    """Per-channel symmetric int8: |x - deq(q(x))| <= scale/2 elementwise
    (round-to-nearest on an absmax-scaled grid), across magnitudes."""
    rs = np.random.RandomState(seed)
    x = (rs.randn(rows, cols) * 10.0 ** scale_pow).astype(np.float32)
    qt = quantize(x, "int8")
    assert qt.values.dtype == jnp.int8
    assert qt.scales.shape == (1, cols)
    err = np.abs(np.asarray(qt.dequantize()) - x)
    bound = np.asarray(qt.scales) * (0.5 + 1e-6) + 1e-30
    assert (err <= bound).all()


def test_quantize_zero_channel_is_identity():
    x = np.zeros((4, 3), np.float32)
    x[:, 1] = 7.0
    qt = quantize(x, "int8")
    np.testing.assert_allclose(np.asarray(qt.dequantize()), x, atol=7 / 254)
    # all-zero channels quantize to exact zeros (scale guard, no NaN)
    assert np.asarray(qt.dequantize())[:, 0].max() == 0.0


def test_per_tensor_matches_legacy_compressor_formula():
    """The shared primitive reproduces optim/compression.py's historical
    int8 math bit-for-bit (per-tensor absmax, round, clip, widen)."""
    rs = np.random.RandomState(1)
    x = (rs.randn(13, 7) * 3).astype(np.float32)
    scale = np.abs(x).max() / 127.0
    legacy = (np.clip(np.round(x / scale), -127, 127)
              .astype(np.int8).astype(np.float32) * scale)
    np.testing.assert_array_equal(np.asarray(fake_quantize(x, axis=None)),
                                  legacy)


def test_compress_still_unbiased_with_error_feedback():
    from repro.optim.compression import compress, ef_init

    rs = np.random.RandomState(2)
    g = {"a": jnp.asarray(rs.randn(8, 8).astype(np.float32)), "b": None}
    err = ef_init(g)
    total = np.zeros((8, 8), np.float32)
    for _ in range(50):
        cg, err = compress(g, err)
        total += np.asarray(cg["a"])
        assert cg["b"] is None
    # EF: the running mean of compressed grads converges to the true grad
    np.testing.assert_allclose(total / 50, np.asarray(g["a"]), atol=2e-2)


def test_fp8_roundtrip_relative_error():
    rs = np.random.RandomState(3)
    x = rs.randn(16, 16).astype(np.float32)
    qt = quantize(x, "fp8")
    assert qt.values.dtype == jnp.float8_e4m3fn
    err = np.abs(np.asarray(qt.dequantize()) - x)
    # e4m3 has a 3-bit mantissa: relative error ~2^-4 of channel absmax
    assert err.max() <= np.abs(x).max() * 0.125 + 1e-6


# ---------------------------------------------------------------------------
# Fused dequant matmul kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M_,K,N", [
    (1, 8, 8), (7, 37, 53), (130, 64, 129), (256, 128, 128),
])
def test_dequant_matmul_matches_oracle_non_aligned(M_, K, N):
    """Interpret-mode kernel vs jnp oracle on shapes that do NOT divide
    the 128x128 block grid: edge blocks must not corrupt valid outputs."""
    rs = np.random.RandomState(M_ + K + N)
    x = rs.randn(M_, K).astype(np.float32)
    qt = quantize(rs.randn(K, N).astype(np.float32), "int8")
    want = ops.dequant_matmul(x, qt.values, qt.scales, impl="jnp")
    got = ops.dequant_matmul(x, qt.values, qt.scales, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dequant_matmul_tolerance_vs_fp32():
    """Against the *unquantized* fp32 matmul, error is bounded by the
    quantization grid: sum_k |x_k| * scale_n / 2 per output element."""
    rs = np.random.RandomState(7)
    x = rs.randn(9, 33).astype(np.float32)
    w = rs.randn(33, 21).astype(np.float32)
    qt = quantize(w, "int8")
    got = np.asarray(ops.dequant_matmul(x, qt.values, qt.scales, impl="jnp"))
    bound = (np.abs(x).sum(1, keepdims=True)
             * np.asarray(qt.scales) * (0.5 + 1e-6))
    assert (np.abs(got - x @ w) <= bound + 1e-6).all()


def test_dequant_matmul_grad_dx_matches_dense(monkeypatch=None):
    rs = np.random.RandomState(11)
    x = jnp.asarray(rs.randn(5, 19).astype(np.float32))
    qt = quantize(rs.randn(19, 23).astype(np.float32), "int8")
    w_deq = np.asarray(qt.dequantize())

    for impl in ("jnp", "interpret"):
        g = jax.grad(lambda x: jnp.sum(jnp.sin(
            ops.dequant_matmul(x, qt.values, qt.scales, impl=impl))))(x)
        gd = jax.grad(lambda x: jnp.sum(jnp.sin(x @ w_deq)))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                                   rtol=1e-5, atol=1e-5, err_msg=impl)


# ---------------------------------------------------------------------------
# Tree quantization
# ---------------------------------------------------------------------------


def test_quantize_tree_allowlist_and_idempotence():
    cfg = tiny_cfg()
    params = M.init_params(KEY, cfg)
    q = quantize_tree(params)
    seen_q = sum(quantizable(p) for p, _ in tu.flatten_with_paths(params))
    qs = quant_summary(q)
    assert qs["n_quantized_leaves"] == seen_q > 0
    # adapter / norm / embed leaves stay dense fp32
    for path, leaf in tu.flatten_with_paths(q):
        if "/adapter/" in path or "norm" in path or "embed" in path:
            assert not path.endswith(("/values", "/scales")), path
    # idempotent: re-quantizing changes nothing
    q2 = quantize_tree(q)
    for (p1, a), (p2, b) in zip(tu.flatten_with_paths(q),
                                tu.flatten_with_paths(q2)):
        assert p1 == p2
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_requantize_broad_pattern_cannot_touch_scales():
    """QTensor nodes are flattened as whole leaves: even an unanchored
    custom pattern that matches component paths (`.../wi/scales`) must
    pass existing QTensors through instead of quantizing their scales."""
    rs = np.random.RandomState(0)
    tree = {"blocks": {"mlp": {"wi": jnp.asarray(
        rs.randn(8, 8).astype(np.float32))}}}
    q1 = quantize_tree(tree, patterns=(r"/mlp/",))
    assert is_qtensor(q1["blocks"]["mlp"]["wi"])
    q2 = quantize_tree(q1, patterns=(r"/mlp/",))
    wi = q2["blocks"]["mlp"]["wi"]
    assert is_qtensor(wi) and not is_qtensor(wi.scales)
    np.testing.assert_array_equal(np.asarray(wi.values),
                                  np.asarray(q1["blocks"]["mlp"]["wi"].values))


def test_dequantize_tree_roundtrip_bounded():
    cfg = tiny_cfg()
    params = M.init_params(KEY, cfg)
    deq = dequantize_tree(quantize_tree(params))
    for (path, a), (_, b) in zip(tu.flatten_with_paths(deq),
                                 tu.flatten_with_paths(params)):
        a, b = np.asarray(a), np.asarray(b)
        if quantizable(path):
            assert np.abs(a - b).max() <= np.abs(b).max() / 127 + 1e-6, path
        else:
            np.testing.assert_array_equal(a, b, err_msg=path)


def test_forward_parity_quantized_tree_bounded():
    """Full forward with a quantized tree stays close to fp32 logits."""
    cfg = tiny_cfg()
    params = M.init_params(KEY, cfg)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 97, (2, 12)))
    ref, _ = M.forward_lm(params, cfg, toks)
    got, _ = M.forward_lm(quantize_tree(params), cfg, toks)
    assert float(jnp.max(jnp.abs(got - ref))) < 0.15


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------


def test_calibration_collects_per_tag_stats_and_tightens_error():
    cfg = tiny_cfg()
    params = M.init_params(KEY, cfg)
    rs = np.random.RandomState(0)
    batches = [{"tokens": rs.randint(0, 97, (2, 12))} for _ in range(3)]
    stats = calibrate(cfg, params, iter(batches), max_batches=3)

    tags = {tag_of(p) for p, _ in tu.flatten_with_paths(params)
            if quantizable(p)}
    assert tags <= set(stats)  # every quantizable call site was observed
    assert stats["mlp/wo"].shape == (cfg.d_ff,)
    assert stats["attn/wq"].shape == (cfg.d_model,)
    assert all(np.all(np.isfinite(v)) and np.all(v >= 0)
               for v in stats.values())

    # the weighted clip search never degrades the weighted error metric
    q_cal = quantize_tree(params, stats=stats)
    q_abs = quantize_tree(params)
    for (path, leaf) in tu.flatten_with_paths(params):
        if not quantizable(path):
            continue
        m = stats[tag_of(path)].reshape(-1, 1)

        def werr(qtree):
            node = qtree
            for part in path.split("/"):
                node = node[part]
            d = np.asarray(node.dequantize()) - np.asarray(leaf)
            return float((m * np.square(d)).sum())

        assert werr(q_cal) <= werr(q_abs) + 1e-12, path


def test_collector_not_active_outside_context():
    from repro.quant.calibrate import collecting

    assert not collecting()
    with pytest.raises(RuntimeError):
        from repro.quant.calibrate import collect_stats

        with collect_stats():
            with collect_stats():
                pass


# ---------------------------------------------------------------------------
# Sharding specs for QTensor component paths
# ---------------------------------------------------------------------------


def test_param_spec_values_and_scales():
    from types import SimpleNamespace

    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import param_spec

    mesh = SimpleNamespace(axis_names=("data", "model"),
                           devices=SimpleNamespace(shape=(2, 4)))
    cfg = SimpleNamespace(shard_profile="tp")

    # column-parallel: values and scales both shard the output channels
    assert param_spec("blocks/g0/slot0/mlp/wi/values",
                      (2, 64, 128), cfg, mesh) == P(None, None, "model")
    assert param_spec("blocks/g0/slot0/mlp/wi/scales",
                      (2, 1, 128), cfg, mesh) == P(None, None, "model")
    # row-parallel: values shard the contraction dim; the scales' collapsed
    # contraction dim fails fit_spec -> replicated along the sharded axis
    assert param_spec("blocks/g0/slot0/attn/wo/values",
                      (2, 64, 64), cfg, mesh) == P(None, "model", None)
    assert "model" not in param_spec("blocks/g0/slot0/attn/wo/scales",
                                     (2, 1, 64), cfg, mesh)
    # fit_spec fallback: indivisible output dim -> both replicated
    assert "model" not in param_spec("blocks/g0/slot0/mlp/wi/values",
                                     (2, 64, 126), cfg, mesh)
    # adapters never quantize, but their spec must stay replicated even if
    # a values-suffixed path ever showed up under /adapter/
    assert param_spec("blocks/g0/slot0/adapter/w/values",
                      (2, 64), cfg, mesh) == P()


def test_params_shardings_cover_quantized_tree():
    """params_shardings must produce a structurally-matching sharding tree
    for a quantized param tree (device_put target under a mesh)."""
    from jax.sharding import Mesh

    cfg = tiny_cfg()
    params = quantize_tree(M.init_params(KEY, cfg))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    from repro.dist.sharding import params_shardings

    sh = params_shardings(params, cfg, mesh)
    placed = jax.device_put(params, sh)
    for (p, a), (_, b) in zip(tu.flatten_with_paths(placed),
                              tu.flatten_with_paths(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=p)


# ---------------------------------------------------------------------------
# QPEFT gradient flow
# ---------------------------------------------------------------------------


def _snap_to_grid(params):
    """Force every quantizable leaf onto an exact power-of-two int8 grid
    so quantization is lossless (used by parity tests)."""

    def snap(path, leaf):
        if not quantizable(path):
            return leaf
        rs = np.random.RandomState(
            np.frombuffer(path.encode()[-4:].rjust(4, b"\0"),
                          np.uint32)[0] % 2**31)
        v = rs.randint(-127, 128, size=leaf.shape).astype(np.float32)
        v[..., 0, :] = 127.0  # pin the per-channel absmax to the grid edge
        e = rs.randint(-8, -3, size=leaf.shape[:-2] + (1, leaf.shape[-1]))
        return jnp.asarray(v * (2.0 ** e).astype(np.float32))

    return tu.map_with_path(snap, params)


def test_qpeft_frozen_untouched_and_adapter_grads_exact():
    """The gradient-flow contract: training with an int8 trunk leaves the
    quantized leaves bit-identical, and (on a losslessly-quantizable
    trunk) produces bit-identical adapter updates to fp32 training."""
    from repro.core import peft
    from repro.train.steps import build_train_step, make_state

    cfg = tiny_cfg()
    ocfg = OptimCfg(lr=1e-2, total_steps=4)
    strat = peft.strategy("hadamard")
    base = _snap_to_grid(M.init_params(KEY, cfg))
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 97, (4, 16))
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}

    s_fp = make_state(KEY, cfg, strat, ocfg, params=base)
    s_q = make_state(KEY, cfg, strat, ocfg, params=base, quant="int8")
    frozen0 = jax.tree.map(np.asarray, s_q["frozen"])
    assert quant_summary(s_q["frozen"])["n_quantized_leaves"] > 0
    assert quant_summary(s_fp["frozen"])["n_quantized_leaves"] == 0

    step = build_train_step(cfg, ocfg)
    for _ in range(3):
        s_fp, m_fp = step(s_fp, batch)
        s_q, m_q = step(s_q, batch)

    # 1. quantized leaves untouched by training
    for (p, a), (_, b) in zip(tu.flatten_with_paths(frozen0),
                              tu.flatten_with_paths(s_q["frozen"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=p)
    # 2. adapter grads/updates exact vs the fp32 run (lossless trunk)
    for (p, a), (_, b) in zip(tu.flatten_with_paths(s_fp["trainable"]),
                              tu.flatten_with_paths(s_q["trainable"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=p)
    np.testing.assert_array_equal(np.asarray(m_fp["loss"]),
                                  np.asarray(m_q["loss"]))


def test_make_state_rejects_quant_with_trainable_trunk():
    from repro.core import peft
    from repro.train.steps import make_state

    cfg = tiny_cfg()
    with pytest.raises(ValueError, match="quantized nothing"):
        make_state(KEY, cfg, peft.strategy("full"),
                   OptimCfg(total_steps=2), quant="int8")


def test_unknown_mode_and_bad_qdense_operand_raise():
    from repro.quant import qdense

    with pytest.raises(ValueError, match="unknown quantization mode"):
        quantize(np.ones((2, 2), np.float32), "int4")
    stacked = quantize(np.ones((2, 4, 4), np.float32))
    with pytest.raises(ValueError, match="2D QTensor"):
        qdense(jnp.ones((3, 4)), stacked)


def test_quantization_error_scalar():
    from repro.quant import quantization_error

    rs = np.random.RandomState(5)
    x = rs.randn(8, 8).astype(np.float32)
    qt = quantize(x)
    e = float(quantization_error(x, qt))
    assert 0.0 <= e <= float(np.square(np.asarray(qt.scales)).max())
    # snapped input: zero error
    snapped = np.asarray(qt.dequantize())
    assert float(quantization_error(snapped, quantize(snapped))) == 0.0


def test_calibration_encoder_family():
    """The calibration driver routes encoder configs through
    forward_encoder (pooler/classifier untouched, attn/mlp tags seen)."""
    from repro.configs import PAPER

    cfg = PAPER["bert-tiny"]()
    params = M.init_params(KEY, cfg)
    rs = np.random.RandomState(0)
    batches = [{"tokens": rs.randint(0, cfg.vocab_size, (2, 8)),
                "type_ids": np.zeros((2, 8), np.int32)} for _ in range(2)]
    stats = calibrate(cfg, params, iter(batches), max_batches=2)
    assert {"attn/wq", "mlp/wi", "mlp/wo"} <= set(stats)
    # pooler/classifier are not quantizable call sites
    assert not any(t.startswith(("pooler", "classifier")) for t in stats)


def test_is_qtensor_and_summary():
    qt = quantize(np.ones((4, 4), np.float32))
    assert is_qtensor(qt) and not is_qtensor(np.ones(3))
    s = quant_summary({"a": qt, "b": jnp.ones((2, 2))})
    assert s["n_quantized_leaves"] == 1
    assert s["dense_bytes_fp32"] == 64
    assert s["quantized_bytes"] == 16 + 16  # int8 payload + (1,4) fp32 scales
    assert s["ratio"] == pytest.approx(2.0)
