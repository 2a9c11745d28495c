"""Seeded random weights, made on the device in one jitted call, laid out
as the program's parameter tree names them.

The layout is written out here from the configuration file alone, so the
plain references can rebuild the same weights without the program;
`check_layout` holds it against the program's own tree at set-up.
Matrices and embeddings are N(0, initializer_range) (the Hugging Face
initialisation), norms 1, biases 0. Hadamard adapters are the identity
for training; a serving bank gives each tenant w = 1 + 0.05 N(0, 1) and
b = 0.05 N(0, 1), as if each were fine-tuned on its own task.
"""
from __future__ import annotations

import zlib

import numpy as np

ADAPTER_SCALE = 0.05
STACK = "blocks/g0/slot0/"


def decoder_layout(conf: dict, tenants: int) -> dict:
    """path -> (shape, dtype, init) for a pre-norm GQA decoder with qk-norm,
    a gated MLP, tied embeddings and a bank of `tenants` adapters."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    H, KH = conf["num_attention_heads"], conf["num_key_value_heads"]
    Dh, ff, V = conf["head_dim"], conf["intermediate_size"], conf["vocab_size"]
    p = conf["dtype"]["param"]
    a = conf["dtype"]["adapter"]
    out = {
        "embed/table": ((V, d), p, "normal"),
        "final_norm/scale": ((d,), p, "ones"),
        STACK + "attn_norm/scale": ((L, d), p, "ones"),
        STACK + "ffn_norm/scale": ((L, d), p, "ones"),
        STACK + "attn/wq": ((L, d, H * Dh), p, "normal"),
        STACK + "attn/wk": ((L, d, KH * Dh), p, "normal"),
        STACK + "attn/wv": ((L, d, KH * Dh), p, "normal"),
        STACK + "attn/wo": ((L, H * Dh, d), p, "normal"),
        STACK + "attn/q_norm": ((L, Dh), p, "ones"),
        STACK + "attn/k_norm": ((L, Dh), p, "ones"),
        STACK + "mlp/wi": ((L, d, ff), p, "normal"),   # gate (under silu)
        STACK + "mlp/wg": ((L, d, ff), p, "normal"),   # up
        STACK + "mlp/wo": ((L, ff, d), p, "normal"),   # down
        STACK + "adapter/w": ((L, tenants, d), a, "tenant_w"),
        STACK + "adapter/b": ((L, tenants, d), a, "tenant_b"),
    }
    return out


def encoder_layout(conf: dict) -> dict:
    """path -> (shape, dtype, init) for a post-LN BERT/RoBERTa encoder with
    a pooler, a two-class head and identity Hadamard adapters."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    ff, V = conf["intermediate_size"], conf["vocab_size"]
    P, T = conf["max_position_embeddings"], conf["type_vocab_size"]
    p = conf["dtype"]["param"]
    a = conf["dtype"]["adapter"]
    C = conf["num_labels"]
    out = {
        "embed/table": ((V, d), p, "normal"),
        "pos_embed/table": ((P, d), p, "normal"),
        "type_embed/table": ((T, d), p, "normal"),
        "embed_norm/scale": ((d,), p, "ones"),
        "embed_norm/bias": ((d,), p, "zeros"),
        "final_norm/scale": ((d,), p, "ones"),
        "final_norm/bias": ((d,), p, "zeros"),
        "pooler/kernel": ((d, d), p, "normal"),
        "pooler/bias": ((d,), p, "zeros"),
        "classifier/kernel": ((d, C), "float32", "normal"),
        "classifier/bias": ((C,), "float32", "zeros"),
        STACK + "adapter/w": ((L, d), a, "ones"),
        STACK + "adapter/b": ((L, d), a, "zeros"),
    }
    for n in ("attn_norm", "ffn_norm"):
        out[STACK + n + "/scale"] = ((L, d), p, "ones")
        out[STACK + n + "/bias"] = ((L, d), p, "zeros")
    for n in ("wq", "wk", "wv", "wo"):
        out[STACK + "attn/" + n] = ((L, d, d), p, "normal")
    for n in ("bq", "bk", "bv", "bo"):
        out[STACK + "attn/" + n] = ((L, d), p, "zeros")
    out[STACK + "mlp/wi"] = ((L, d, ff), p, "normal")
    out[STACK + "mlp/wo"] = ((L, ff, d), p, "normal")
    out[STACK + "mlp/bi"] = ((L, ff), p, "zeros")
    out[STACK + "mlp/bo"] = ((L, d), p, "zeros")
    return out


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
