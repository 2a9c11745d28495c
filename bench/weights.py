"""Seeded random weights, made on the device in one jitted call, laid out
as the program's parameter tree names them.

Each architecture's module (`bench/arch/<model_type>.py`) writes its
layout out from the configuration file alone, so the plain references
can rebuild the same weights without the program; `check_layout` holds
it against the program's own tree at set-up.
Matrices and embeddings are N(0, initializer_range) (the Hugging Face
initialisation), norms 1, biases 0. Hadamard adapters are the identity
for training; a serving bank gives each tenant w = 1 + 0.05 N(0, 1) and
b = 0.05 N(0, 1), as if each were fine-tuned on its own task.
"""
from __future__ import annotations

import zlib

import numpy as np

ADAPTER_SCALE = 0.05


def stack(group: int = 0, slot: int = 0) -> str:
    """The path prefix of slot `slot`'s stacked leaves in group `group` of
    the program's layer pattern."""
    return f"blocks/g{group}/slot{slot}/"


def by_group(flat: dict, slot: int = 0) -> list:
    """The stacked leaves of slot `slot` of each group of a flat tree, in
    the groups' order: one dict per group, keyed by the path inside the
    slot."""
    groups: dict = {}
    for path, v in flat.items():
        if path.startswith("blocks/"):
            _, g, s, name = path.split("/", 3)
            if s == f"slot{slot}":
                groups.setdefault(int(g[1:]), {})[name] = v
    return [groups[g] for g in sorted(groups)]


def _leaf(key, path, shape, dtype, init, std):
    import jax
    import jax.numpy as jnp

    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, np.uint32(zlib.crc32(path.encode())))
    z = jax.random.normal(k, shape, jnp.float32)
    if init == "normal":
        return (z * std).astype(dtype)
    if init == "tenant_w":
        return (1.0 + ADAPTER_SCALE * z).astype(dtype)
    if init == "tenant_b":
        return (ADAPTER_SCALE * z).astype(dtype)
    raise ValueError(f"unknown init {init!r} for {path}")


def make(key, layout: dict, std: float, dtype_override=None) -> dict:
    """The nested parameter dict, every leaf made on the device in one
    jitted call. dtype_override casts every leaf (the references take
    the served values in float32)."""
    import jax

    def build(k):
        flat = {}
        for path, (shape, dtype, init) in layout.items():
            leaf = _leaf(k, path, shape, dtype, init, std)
            flat[path] = leaf if dtype_override is None else \
                leaf.astype(dtype_override)
        return flat

    return nest(jax.jit(build)(key))


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *head, last = path.split("/")
        node = out
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + k + "/"))
        else:
            out[prefix + k] = v
    return out


def check_layout(layout: dict, program_shapes: dict) -> None:
    """Raise unless the layout names exactly the program's leaves (a flat
    path -> shape-and-dtype dict), with their shapes and dtypes."""
    import jax.numpy as jnp

    want = {p: (tuple(s.shape), jnp.dtype(s.dtype))
            for p, s in program_shapes.items()}
    have = {p: (tuple(s), jnp.dtype(d)) for p, (s, d, _) in layout.items()}
    if want != have:
        diff = sorted(map(str, set(want.items()) ^ set(have.items())))
        raise ValueError(f"bench weight layout differs from the program's "
                         f"parameter tree: {diff[:8]}")


def program_shapes(cfg, tenants=None) -> dict:
    """The program's parameter tree for `cfg`, abstract and flat; with
    `tenants`, each adapter leaf banked as (..., tenants, d)."""
    import jax
    from repro.models import model as M

    flat = flatten(jax.eval_shape(lambda k: M.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
    if tenants is None:
        return flat
    return {p: (jax.ShapeDtypeStruct(s.shape[:-1] + (tenants, s.shape[-1]),
                                     s.dtype) if "/adapter/" in p else s)
            for p, s in flat.items()}
