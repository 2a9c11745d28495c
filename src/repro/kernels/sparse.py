"""Pallas TPU kernel for masked (redundancy-aware) multi-task Hadamard
serving (repro.sparse).

Same scalar-prefetch structure as kernels/multitask.py - the task-id
array drives the BlockSpec index maps so each request's adapter row is
fetched from the bank straight into VMEM - plus a per-row GATE: bank rows
of pruned tenants pass through as the identity inside the fused op,

    y_i = x_i + g[t_i] * (x_i * (w[t_i] - 1) + b[t_i])

so a mixed sparse/dense batch shares one kernel launch with no branch and
no gather materialization. The banks are viewed as (T, 1, d) and the
gate as a (T, 1, 1) fp32 column, so each request's blocks ((1, 1, d) and
(1, 1, 1)) block only the leading task axis and prefetch like the dense
kernel's adapter rows do.

Like the dense multitask kernel it extends, this is the TPU-facing fused
op (gates from `AdapterBank.gates()`, placed replicated via
`dist.sharding.adapter_gate_shardings`): the portable serving tick
reaches the same math by unpacking pruned rows to identity at insert, so
the kernel's own tests/bench are its oracle-parity contract, not a CPU
decode dependency.

Differentiable: the custom VJP computes dx by re-running the forward
kernel on the cotangent with b=0 (dx = g*w*dy + (1-g)*dy, i.e. the same
masked affine), and dw/db as fp32 segment-sums over the batch in jnp -
the same pallas-forward/jnp-reduction split the fused adapter-norm kernel
uses. The gate and task ids are non-differentiable (float0/zero
cotangents): masks are structural, not trained.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tids_ref, x_ref, w_ref, b_ref, g_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)  # (S, d)
    w = w_ref[0].astype(jnp.float32)  # (1, d)
    b = b_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)  # (1, 1) row gate
    o_ref[0] = (x + g * (x * (w - 1.0) + b)).astype(o_ref.dtype)


def _call(x, w_bank, b_bank, gate, task_ids, interpret: bool):
    B, S, d = x.shape
    T = w_bank.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, S, d), lambda i, tids: (i, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, tids: (tids[i], 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, tids: (tids[i], 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, tids: (tids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, d), lambda i, tids: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, d), x.dtype),
        interpret=interpret,
    )(task_ids.astype(jnp.int32), x, w_bank.reshape(T, 1, d),
      b_bank.reshape(T, 1, d), gate.astype(jnp.float32).reshape(T, 1, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def masked_multitask_hadamard_tpu(x, w_bank, b_bank, gate, task_ids,
                                  interpret: bool):
    """x: (B,S,d); banks: (T,d); gate: (T,) float {0,1}; task_ids: (B,)."""
    return _call(x, w_bank, b_bank, gate, task_ids, interpret)


def _fwd(x, w_bank, b_bank, gate, task_ids, interpret):
    y = _call(x, w_bank, b_bank, gate, task_ids, interpret)
    return y, (x, w_bank, b_bank, gate, task_ids)


def _bwd(interpret, res, dy):
    x, w_bank, b_bank, gate, task_ids = res
    T = w_bank.shape[0]
    # dx is the same masked affine applied to dy with b = 0
    dx = _call(dy, w_bank, jnp.zeros_like(b_bank), gate, task_ids, interpret)
    # dw/db: fp32 per-request reductions over S, segment-summed over tasks
    g = gate.astype(jnp.float32)[task_ids]  # (B,)
    dy32 = dy.astype(jnp.float32)
    per_req_w = g[:, None] * jnp.sum(dy32 * x.astype(jnp.float32), axis=1)
    per_req_b = g[:, None] * jnp.sum(dy32, axis=1)
    dw = jax.ops.segment_sum(per_req_w, task_ids, num_segments=T)
    db = jax.ops.segment_sum(per_req_b, task_ids, num_segments=T)
    return (dx.astype(x.dtype), dw.astype(w_bank.dtype),
            db.astype(b_bank.dtype), jnp.zeros_like(gate),
            np.zeros(task_ids.shape, jax.dtypes.float0))


masked_multitask_hadamard_tpu.defvjp(_fwd, _bwd)
