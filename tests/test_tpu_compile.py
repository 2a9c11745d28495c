"""Compile every Pallas kernel in `kernels/ops.py` for a described TPU v5e.

Interpret-mode parity tests cannot see what the TPU compiler refuses:
block shapes that break the (8, 128) tiling rule, VMEM overflows,
unsupported dtype conversions. These tests lower each kernel with
`impl="pallas"` (interpret=False) at qwen3-0.6b / bert-base / rwkv6-1.6b
widths against a v5e topology that is described, not attached, and ask
the TPU compiler for an executable. Nothing runs; no chip is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and a module that
decided at import whether its tests exist would give pytest-xdist workers
different collections.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# qwen3-0.6b widths
D_MODEL, N_HEADS, N_KV, HEAD_DIM, D_FF = 1024, 16, 8, 128, 3072
SLOTS, PAGE, CACHE = 4, 16, 1024
# bert-base widths
BERT_D, BERT_BATCH, BERT_SEQ = 768, 32, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip):
    """compile_tpu(fn, *(shape, dtype)) -> the compiled TPU executable."""

    def run(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return run


BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float8_e4m3fn],
                         ids=["int8", "fp8"])
@pytest.mark.parametrize("m", [SLOTS, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["mlp_wi", "mlp_wo"])
def test_dequant_matmul(compile_tpu, dtype, m, k, n):
    compile_tpu(lambda x, v, s: ops.dequant_matmul(x, v, s, impl="pallas"),
                ((m, k), BF16), ((k, n), dtype), ((1, n), F32))


@pytest.mark.parametrize("window,cap", [(None, 0.0), (256, 50.0)],
                         ids=["causal", "window_cap"])
def test_flash_attention(compile_tpu, window, cap):
    compile_tpu(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            window=window, cap=cap,
                                            impl="pallas"),
        ((1, N_HEADS, CACHE, HEAD_DIM), BF16),
        ((1, N_KV, CACHE, HEAD_DIM), BF16),
        ((1, N_KV, CACHE, HEAD_DIM), BF16))


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_paged_attention(compile_tpu, kv):
    nbt = CACHE // PAGE
    n_blocks = 1 + SLOTS * nbt
    pool = ((n_blocks, PAGE, N_KV, HEAD_DIM), I8 if kv == "int8" else F32)
    specs = [((SLOTS, N_HEADS, HEAD_DIM), F32), pool, pool,
             ((SLOTS, nbt), I32), ((SLOTS,), I32)]
    if kv == "int8":
        specs += [((n_blocks, PAGE, N_KV, 1), F32)] * 2

        def fn(q, kp, vp, tbl, kl, ks, vs):
            return ops.paged_attention(q, kp, vp, tbl, kl, k_scales=ks,
                                       v_scales=vs, impl="pallas")
    else:
        def fn(q, kp, vp, tbl, kl):
            return ops.paged_attention(q, kp, vp, tbl, kl, impl="pallas")
    compile_tpu(fn, *specs)


def test_hadamard_affine_forward_and_backward(compile_tpu):
    def fwd_bwd(x, w, b):
        y, vjp = jax.vjp(lambda *a: ops.hadamard(*a, impl="pallas"), x, w, b)
        return y, vjp(y)

    compile_tpu(fwd_bwd, ((BERT_BATCH, BERT_SEQ, BERT_D), BF16),
                ((BERT_D,), F32), ((BERT_D,), F32))


@pytest.mark.parametrize("norm", ["layernorm", "rmsnorm"])
def test_fused_adapter_residual_norm(compile_tpu, norm):
    d = BERT_D if norm == "layernorm" else D_MODEL
    vec = ((d,), F32)
    act = ((BERT_BATCH, BERT_SEQ, d), BF16)
    if norm == "layernorm":
        def fn(x, res, w, b, scale, bias):
            return ops.fused_adapter_norm(x, res, w, b, scale, bias=bias,
                                          impl="pallas")
        compile_tpu(fn, act, act, vec, vec, vec, vec)
    else:
        def fn(x, res, w, b, scale):
            return ops.fused_adapter_norm(x, res, w, b, scale, impl="pallas")
        compile_tpu(fn, act, act, vec, vec, vec)


def test_multitask_hadamard(compile_tpu):
    compile_tpu(
        lambda x, w, b, t: ops.multitask_hadamard(x, w, b, t, impl="pallas"),
        ((SLOTS, 64, D_MODEL), BF16), ((3, D_MODEL), F32),
        ((3, D_MODEL), F32), ((SLOTS,), I32))


def test_masked_multitask_hadamard_forward_and_backward(compile_tpu):
    def fwd_bwd(x, w, b, g, t):
        y, vjp = jax.vjp(
            lambda x_, w_, b_: ops.masked_multitask_hadamard(
                x_, w_, b_, g, t, impl="pallas"), x, w, b)
        return y, vjp(y)

    compile_tpu(fwd_bwd, ((SLOTS, 64, D_MODEL), BF16), ((3, D_MODEL), F32),
                ((3, D_MODEL), F32), ((3,), F32), ((SLOTS,), I32))


def test_wkv6(compile_tpu):
    # rwkv6-1.6b: d_model 2048 in 32 heads of 64
    h, n, t = 32, 64, 256
    act = ((1, h, t, n), BF16)
    compile_tpu(lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u, impl="pallas"),
                act, act, act, act, ((h, n), F32))
