"""Production mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state. Single pod: 256 chips as (data=16, model=16).
Multi-pod: 2 pods = 512 chips as (pod=2, data=16, model=16); the batch is
sharded over (pod, data) and cross-pod traffic is the (tiny, for PEFT)
gradient all-reduce plus any FSDP weight gathers kept intra-pod by axis
ordering.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 4):
    """(data, model) mesh over the first data*model local devices: TPU
    chips, or CPU devices forced with XLA_FLAGS=
    --xla_force_host_platform_device_count=N. Raises when there are too
    few devices - a "sharded" run must never quietly run unsharded."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"a {data}x{model} mesh needs {data * model} "
                         f"devices; {n} available")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto),
                         devices=jax.devices()[:data * model])


def parse_mesh(spec: str):
    """CLI mesh spec: '' -> None; 'DxM' (e.g. '2x4') -> (data, model)
    mesh from `make_host_mesh`."""
    if not spec:
        return None
    data, model = (int(v) for v in spec.lower().split("x"))
    return make_host_mesh(data, model)
