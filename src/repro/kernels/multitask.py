"""Pallas TPU kernel for batched multi-task Hadamard serving.

Each request in the batch carries a task id; its tokens must be transformed
by that task's (w, b). The kernel uses scalar prefetch so the task-id array
drives the BlockSpec index maps: the adapter row for request i is fetched
from the bank directly into VMEM - no gather materialization of (B, d)
adapter tensors in HBM. The banks are viewed as (T, 1, d) so each
request's row is a (1, 1, d) block: the blocked task axis is a leading
dim, and the trailing (1, d) block covers the whole trailing array dims,
as the TPU block-shape rule requires.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tids_ref, x_ref, w_ref, b_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)  # (S, d)
    w = w_ref[0].astype(jnp.float32)  # (1, d)
    b = b_ref[0].astype(jnp.float32)
    o_ref[0] = (x * w + b).astype(o_ref.dtype)


def multitask_hadamard_tpu(x, w_bank, b_bank, task_ids, *, interpret: bool):
    """x: (B,S,d); banks: (T,d); task_ids: (B,) int32."""
    B, S, d = x.shape
    T = w_bank.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, S, d), lambda i, tids: (i, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, tids: (tids[i], 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, tids: (tids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, d), lambda i, tids: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, d), x.dtype),
        interpret=interpret,
    )(task_ids.astype(jnp.int32), x, w_bank.reshape(T, 1, d),
      b_bank.reshape(T, 1, d))
