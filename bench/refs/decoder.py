"""Plain float32 reference of a pre-norm GQA decoder (Qwen3): RMSNorm,
q/k RMSNorm per head, rotary embeddings (rotate-half), causal softmax
attention, a SiLU-gated MLP, tied embeddings, and the Hadamard adapter
`y * w + b` on the attention block's output with each row's own tenant.

Straight jax.numpy at `highest` matmul precision, no cache, no kernels,
nothing of the program: the weights are rebuilt from the seed by
`bench/weights.py`, from the layout the architecture's module gives. It
runs one layer at a time, through the layout's groups in order, over
rows padded to one length, and reads logits only at the positions that
predicted a served token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F32 = jnp.float32


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (B, T, heads, Dh) at positions 0..T-1."""
    T, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=F32) / Dh)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., Dh // 2:], x[..., :Dh // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def layer(S, l, x, rows, conf):
    """Layer l of one group's stacked weights S (keyed by the path inside
    the slot) over x (B, T, d); rows (B,) are the tenants' bank rows."""
    B, T, d = x.shape
    H, KH, Dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    eps = conf["rms_norm_eps"]
    w = {k: v[l] for k, v in S.items()}
    h = rms(x, w["attn_norm/scale"], eps)
    q = (h @ w["attn/wq"]).reshape(B, T, H, Dh)
    k = (h @ w["attn/wk"]).reshape(B, T, KH, Dh)
    v = (h @ w["attn/wv"]).reshape(B, T, KH, Dh)
    q = rope(rms(q, w["attn/q_norm"], eps), conf["rope_theta"])
    k = rope(rms(k, w["attn/k_norm"], eps), conf["rope_theta"])
    k = jnp.repeat(k, H // KH, axis=2)
    v = jnp.repeat(v, H // KH, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    a = o.reshape(B, T, H * Dh) @ w["attn/wo"]
    a = a * w["adapter/w"][rows][:, None] + w["adapter/b"][rows][:, None]
    x = x + a
    h = rms(x, w["ffn_norm/scale"], eps)
    f = jax.nn.silu(h @ w["mlp/wi"]) * (h @ w["mlp/wg"])
    return x + f @ w["mlp/wo"]


MATMULS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/wi", "mlp/wg",
           "mlp/wo")


def fp8_weights(S: dict) -> dict:
    """One group's matmul weights rounded to float8 e4m3 with one scale per
    output channel (absmax to 448), back in float32; the rest unchanged."""
    def q(w):
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
        s = jnp.where(s == 0, 1.0, s)
        return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s

    return {k: (q(v) if k in MATMULS else v) for k, v in S.items()}


def served_gaps(conf: dict, layout: dict, key, std: float, requests,
                length: int, max_new: int, control: bool = False,
                batch: int = 4):
    """Over the weights `layout` makes from `key`, for each (prompt, served
    tokens, tenant): at every served token, the reference's best logit
    less the logit of the token served. With `control`, also the same
    reference with float8 matmul weights in the program's place: at the
    same positions, the reference's best logit less the logit of the
    token the float8 model puts first. Returns a list of float32 gap
    arrays per request, and that of the control (or None)."""
    W = weights.flatten(weights.make(key, layout, std, dtype_override=F32))
    stacks = weights.by_group(W)
    stacks_c = [fp8_weights(S) for S in stacks] if control else None
    eps = conf["rms_norm_eps"]
    run_layer = jax.jit(lambda S, l, x, rows: layer(S, l, x, rows, conf))

    @jax.jit
    def gaps_of(h, hc, at, toks, final_norm, table):
        logits = rms(h[at], final_norm, eps) @ table.T
        best = jnp.max(logits, -1)
        served = jnp.take_along_axis(logits, toks[:, None], -1)[:, 0]
        first = jnp.argmax(rms(hc[at], final_norm, eps) @ table.T, -1)
        ctl = jnp.take_along_axis(logits, first[:, None], -1)[:, 0]
        return best - served, best - ctl

    def run(stacks, toks, rows):
        x = W["embed/table"][jnp.asarray(toks)]
        for S in stacks:
            for l in range(S["attn_norm/scale"].shape[0]):
                x = run_layer(S, jnp.int32(l), x, jnp.asarray(rows))
        return x

    out, out_c = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(requests), batch):
            chunk = requests[i:i + batch]
            toks = np.zeros((batch, length), np.int32)
            rows = np.zeros((batch,), np.int32)
            for j, (prompt, served, tenant) in enumerate(chunk):
                seq = np.concatenate([prompt, served])
                toks[j, :len(seq)] = seq
                rows[j] = tenant
            x = run(stacks, toks, rows)
            xc = run(stacks_c, toks, rows) if control else x
            for j, (prompt, served, _) in enumerate(chunk):
                S, n = len(prompt), len(served)
                at = np.zeros((max_new,), np.int32)
                tk = np.zeros((max_new,), np.int32)
                at[:n] = np.arange(S - 1, S + n - 1)
                tk[:n] = served
                g, gc = gaps_of(x[j], xc[j], jnp.asarray(at), jnp.asarray(tk),
                                W["final_norm/scale"], W["embed/table"])
                out.append(np.asarray(g)[:n])
                out_c.append(np.asarray(gc)[:n])
    return out, (out_c if control else None)
