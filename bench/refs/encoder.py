"""Plain float32 reference of Hadamard-adapter training on a post-LN
BERT/RoBERTa encoder classifier.

Forward: word + position + type embeddings and LayerNorm; per layer,
softmax attention over every position with biases, the adapter
`y * w + b` on the attention output, LayerNorm(x + a), a tanh-GELU MLP,
LayerNorm(x + f); a tanh pooler on the first token and a linear head;
mean cross-entropy. Training: gradients of the adapter's w, b and the
FFN-output LayerNorm's scale and bias (the paper's stage 2), clipped to
a global norm, then bias-corrected Adam at a constant learning rate
without weight decay.

Straight jax.numpy at `highest` matmul precision, nothing of the
program: the weights are rebuilt from the seed by `bench/weights.py`,
from the layout the architecture's module gives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F32 = jnp.float32
P = weights.stack()
TRAINABLE = (P + "adapter/w", P + "adapter/b", P + "ffn_norm/scale",
             P + "ffn_norm/bias")


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3)))


def loss(W: dict, conf: dict, tokens, type_ids, labels):
    eps, H = conf["layer_norm_eps"], conf["num_attention_heads"]
    B, S = tokens.shape
    x = (W["embed/table"][tokens] + W["pos_embed/table"][jnp.arange(S)]
         + W["type_embed/table"][type_ids])
    x = layer_norm(x, W["embed_norm/scale"], W["embed_norm/bias"], eps)
    stacked = {k[len(P):]: v for k, v in W.items() if k.startswith(P)}

    def block(x, w):
        d = x.shape[-1]
        Dh = d // H

        def proj(n):
            return (x @ w["attn/w" + n] + w["attn/b" + n]).reshape(B, S, H, Dh)

        s = jnp.einsum("bqhd,bkhd->bhqk", proj("q"), proj("k")) / np.sqrt(Dh)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), proj("v"))
        a = o.reshape(B, S, d) @ w["attn/wo"] + w["attn/bo"]
        a = a * w["adapter/w"] + w["adapter/b"]
        x = layer_norm(x + a, w["attn_norm/scale"], w["attn_norm/bias"], eps)
        f = gelu_tanh(x @ w["mlp/wi"] + w["mlp/bi"]) @ w["mlp/wo"] + w["mlp/bo"]
        x = layer_norm(x + f, w["ffn_norm/scale"], w["ffn_norm/bias"], eps)
        return x, None

    x, _ = jax.lax.scan(block, x, stacked)
    pooled = jnp.tanh(x[:, 0] @ W["pooler/kernel"] + W["pooler/bias"])
    logits = pooled @ W["classifier/kernel"] + W["classifier/bias"]
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - picked)


def train(conf: dict, layout: dict, key, std: float, batches, optim: dict):
    """Run len(batches) steps from the weights `layout` makes from `key`.
    Returns (losses, first step's clipped gradient per trainable leaf,
    parameter change per trainable leaf after the last step), as float32
    numpy arrays keyed by path."""
    W = weights.flatten(weights.make(key, layout, std, dtype_override=F32))
    frozen = {k: v for k, v in W.items() if k not in TRAINABLE}
    params = {k: W[k] for k in TRAINABLE}
    b1, b2, eps, lr = optim["b1"], optim["b2"], optim["eps"], optim["lr"]

    @jax.jit
    def step(params, m, v, t, batch, frozen):
        value, g = jax.value_and_grad(
            lambda p: loss({**frozen, **p}, conf, batch["tokens"],
                           batch["type_ids"], batch["labels"]))(params)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        g = {k: x * jnp.minimum(1.0, optim["grad_clip"] / (norm + 1e-9))
             for k, x in g.items()}
        m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
        v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in g}
        new = {k: params[k] - lr * (m[k] / (1 - b1 ** t))
               / (jnp.sqrt(v[k] / (1 - b2 ** t)) + eps) for k in g}
        return new, m, v, value, g

    zeros = {k: jnp.zeros_like(x) for k, x in params.items()}
    p, m, v = params, zeros, zeros
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            p, m, v, value, g = step(p, m, v, jnp.float32(t),
                                     jax.tree.map(jnp.asarray, batch), frozen)
            losses.append(float(value))
            if first_grad is None:
                first_grad = {k: np.asarray(x) for k, x in g.items()}
    change = {k: np.asarray(p[k] - params[k]) for k in params}
    return losses, first_grad, change
