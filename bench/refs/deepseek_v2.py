"""Plain float32 reference of DeepSeek-V2 as one chip of an expert-parallel
deployment serves it: multi-head latent attention with an uncompressed
query, YaRN rope, a dense SiLU-gated first layer, then DeepSeekMoE layers
(softmax router over every published expert, greedy top-k, no
renormalisation, routed scale 1, shared experts), the Hadamard adapter
`y * w + b` on the attention block's output with each row's own tenant,
final RMSNorm and an untied head.

Straight jax.numpy at `highest` matmul precision, no cache, no kernels,
nothing of the program. The attention is the published, non-absorbed
form: per head K = [c W_UK, k_pe] and V = c W_UV from the normed latent
c. YaRN follows the published DeepseekV2YarnRotaryEmbedding, the rope
dims de-interleaved before rotate-half. The MoE layer holds the same
share as the program: experts first..first+held-1 of the router's
`published` outputs, each run over every token and weighted by the
token's gate (zero where the token did not choose it), plus the shared
experts; what the experts held on other chips would add is left out, in
the program and here alike.

The weights are rebuilt from the seed by `bench/weights.py` in the dtypes
the program holds (bfloat16) and upcast one layer at a time: a float32
copy of every weight would not fit beside the control on one chip.
"""
from __future__ import annotations

import gc
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F32 = jnp.float32


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_mscale(scale, mscale=1.0):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(conf: dict):
    """(inverse frequencies, cos/sin scale, softmax scale), transcribed from
    the published modelling code."""
    dim, base = conf["qk_rope_head_dim"], conf["rope_theta"]
    y = conf["rope_scaling"]
    scale = (conf["qk_nope_head_dim"] + dim) ** -0.5
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if y is None:
        return extra, 1.0, scale
    factor, orig = y["factor"], y["original_max_position_embeddings"]

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = (extra / factor) * (1 - mask) + extra * mask
    cs = (yarn_mscale(factor, y.get("mscale", 1))
          / yarn_mscale(factor, y.get("mscale_all_dim", 0)))
    if y.get("mscale_all_dim"):
        scale *= yarn_mscale(factor, y["mscale_all_dim"]) ** 2
    return inv, cs, scale


def rope(x, inv, cs):
    """x (B, T, heads, dim) at positions 0..T-1: de-interleave, then
    rotate-half with cos and sin scaled by cs."""
    T, dim = x.shape[1], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv)[None]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * (jnp.cos(emb) * cs) + rot * (jnp.sin(emb) * cs)


def attention(w, h, conf):
    """The published MLA over h (B, T, d), causal, non-absorbed."""
    B, T, _ = h.shape
    H, R = conf["num_attention_heads"], conf["kv_lora_rank"]
    nope, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                    conf["v_head_dim"])
    inv, cs, scale = yarn(conf)
    q = (h @ w["attn/wq"]).reshape(B, T, H, nope + dr)
    kv_a = h @ w["attn/wkv_a"]
    c = rms(kv_a[..., :R], w["attn/kv_norm"], conf["rms_norm_eps"])
    k_pe = rope(kv_a[..., None, R:], inv, cs)                 # (B, T, 1, dr)
    kv = (c @ w["attn/wkv_b"]).reshape(B, T, H, nope + dv)    # per-head K, V
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, dr))], -1)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], inv, cs)], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., nope:])
    return o.reshape(B, T, H * dv) @ w["attn/wo"]


def mlp(h, wi, wg, wo):
    return (jax.nn.silu(h @ wi) * (h @ wg)) @ wo


def moe(w, h, conf, first: int):
    """The held experts' part of each token's top-k mixture, plus the
    shared experts. The router scores all of its columns."""
    k, held = conf["num_experts_per_tok"], conf["n_routed_experts"]
    probs = jax.nn.softmax(h @ w["moe/router"], -1)
    gate, idx = jax.lax.top_k(probs, k)
    if conf["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * conf["routed_scaling_factor"]
    y = mlp(h, w["moe/shared_wi"], w["moe/shared_wg"], w["moe/shared_wo"])
    for e in range(held):
        g = jnp.sum(jnp.where(idx == first + e, gate, 0.0), -1)
        y = y + g[..., None] * mlp(h, w["moe/wi"][e], w["moe/wg"][e],
                                   w["moe/wo"][e])
    return y


def held_margin(w, h, conf, first: int):
    """Per token, the router's probability gap between its k-th and
    (k+1)-th choice where either is a held expert: how near the token is
    to swapping a held expert in or out (inf where neither is held)."""
    k, held = conf["num_experts_per_tok"], conf["n_routed_experts"]
    top, idx = jax.lax.top_k(jax.nn.softmax(h @ w["moe/router"], -1), k + 1)
    edge = idx[..., k - 1:]
    mine = jnp.any((edge >= first) & (edge < first + held), -1)
    return jnp.where(mine, top[..., k - 1] - top[..., k], jnp.inf)


def layer(S, l, x, rows, conf, first: int = 0, control: bool = False,
          margin: bool = False):
    """Layer l of one group's stacked weights S (keyed by the path inside
    the slot, in the program's dtypes) over x (B, T, d); rows (B,) are the
    tenants' bank rows. Upcasts this layer's weights to float32 only
    (with `control`, its matmul weights rounded to float8 first). With
    `margin`, also each token's `held_margin` (inf in a dense layer)."""
    w = {k: v[l].astype(F32) for k, v in S.items()}
    if control:
        w = fp8_weights(w)
    eps = conf["rms_norm_eps"]
    a = attention(w, rms(x, w["attn_norm/scale"], eps), conf)
    x = x + a * w["adapter/w"][rows][:, None] + w["adapter/b"][rows][:, None]
    h = rms(x, w["ffn_norm/scale"], eps)
    if "moe/router" in w:
        x = x + moe(w, h, conf, first)
        return (x, held_margin(w, h, conf, first)) if margin else x
    x = x + mlp(h, w["mlp/wi"], w["mlp/wg"], w["mlp/wo"])
    return (x, jnp.full(x.shape[:2], jnp.inf)) if margin else x


MATMULS = ("attn/wq", "attn/wkv_a", "attn/wkv_b", "attn/wo", "mlp/wi",
           "mlp/wg", "mlp/wo", "moe/wi", "moe/wg", "moe/wo", "moe/shared_wi",
           "moe/shared_wg", "moe/shared_wo")


def fp8_weights(w: dict) -> dict:
    """One layer's float32 matmul weights rounded to float8 e4m3 with one
    scale per output channel (absmax to 448); the router, norms and
    adapters as they are."""
    def q(a):
        s = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 448.0
        s = jnp.where(s == 0, 1.0, s)
        return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s

    return {k: (q(v) if k in MATMULS else v) for k, v in w.items()}


def forward(W: dict, toks, rows, conf, first: int = 0, run_layer=None,
            stacks=None):
    """Final hidden states (before the final norm) of tokens (B, T) with
    tenants' bank rows (B,), from a flat weight dict W."""
    run_layer = run_layer or (lambda S, l, x, r: layer(S, l, x, r, conf, first))
    x = W["embed/table"][jnp.asarray(toks)].astype(F32)
    for S in stacks if stacks is not None else weights.by_group(W):
        for l in range(S["attn_norm/scale"].shape[0]):
            x = run_layer(S, jnp.int32(l), x, jnp.asarray(rows))
    return x


def logits(W: dict, h, conf):
    return rms(h, W["final_norm/scale"].astype(F32), conf["rms_norm_eps"]) \
        @ W["lm_head/kernel"].astype(F32)


def served_gaps(conf: dict, layout: dict, key, std: float, requests,
                length: int, max_new: int, control: bool = False,
                batch: int = 4):
    """Over the weights `layout` makes from `key`, for each (prompt, served
    tokens, tenant): at every served token, the reference's best logit
    less the logit of the token served. With `control`, also the same
    reference with float8 matmul weights in the program's place: at the
    same positions, the reference's best logit less the logit of the
    token the float8 model puts first. Returns a list of float32 gap
    arrays per request, and that of the control (or None).

    It also logs, to stderr, the router's `held_margin` at the widest
    gap's position, smallest over the MoE layers, beside the median of
    that smallest margin over the served tokens of the widest gap's
    batch: a widest gap where the margin is near zero is a routing
    near-tie that rounding flips."""
    # The open-loop serving run deletes the program before it calls here,
    # but it froze its set-up's objects (gc.freeze), and the engine's jitted
    # closures hold it in reference cycles that only an unfrozen
    # collection frees: ~13 GB of weights and pool that this reference
    # needs back on one chip.
    gc.unfreeze()
    gc.collect()
    W = weights.flatten(weights.make(key, layout, std))
    first = conf["expert_parallel"]["first_held_expert"]
    stacks = weights.by_group(W)
    plain = jax.jit(lambda S, l, x, r: layer(S, l, x, r, conf, first))
    rounded = jax.jit(lambda S, l, x, r: layer(S, l, x, r, conf, first,
                                               control=True))
    margins = jax.jit(lambda S, l, x, r: layer(S, l, x, r, conf, first,
                                               margin=True))

    @jax.jit
    def gaps_of(h, hc, at, toks, head):
        lg = logits(head, h[at], conf)
        best = jnp.max(lg, -1)
        served = jnp.take_along_axis(lg, toks[:, None], -1)[:, 0]
        top = jnp.argmax(logits(head, hc[at], conf), -1)
        ctl = jnp.take_along_axis(lg, top[:, None], -1)[:, 0]
        return best - served, best - ctl

    head = {k: W[k] for k in ("final_norm/scale", "lm_head/kernel")}
    out, out_c = [], []
    widest = (-1.0, None)  # (gap, (tokens, rows, row, positions, at))
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(requests), batch):
            chunk = requests[i:i + batch]
            toks = np.zeros((batch, length), np.int32)
            rows = np.zeros((batch,), np.int32)
            for j, (prompt, served, tenant) in enumerate(chunk):
                seq = np.concatenate([prompt, served])
                toks[j, :len(seq)] = seq
                rows[j] = tenant
            x = forward(W, toks, rows, conf, first, plain, stacks)
            xc = forward(W, toks, rows, conf, first, rounded, stacks) \
                if control else x
            for j, (prompt, served, _) in enumerate(chunk):
                S, n = len(prompt), len(served)
                at = np.zeros((max_new,), np.int32)
                tk = np.zeros((max_new,), np.int32)
                at[:n] = np.arange(S - 1, S + n - 1)
                tk[:n] = served
                g, g_ctl = gaps_of(x[j], xc[j], jnp.asarray(at),
                                   jnp.asarray(tk), head)
                out.append(np.asarray(g)[:n])
                out_c.append(np.asarray(g_ctl)[:n])
                if n and out[-1].max() > widest[0]:
                    widest = (float(out[-1].max()), (toks, rows, j, at[:n],
                                                     int(out[-1].argmax())))
        if widest[1] is not None:
            toks, rows, j, at, i = widest[1]
            x = W["embed/table"][jnp.asarray(toks)].astype(F32)
            least = jnp.full(toks.shape, jnp.inf)
            for S in stacks:
                for l in range(S["attn_norm/scale"].shape[0]):
                    x, m = margins(S, jnp.int32(l), x, jnp.asarray(rows))
                    least = jnp.minimum(least, m)
            least = np.asarray(least)[j]
            print(f"reference: widest gap {widest[0]!r} at served token {i}; "
                  f"smallest held router margin there over the MoE layers "
                  f"{float(least[at[i]])!r}, median over its request's "
                  f"served tokens {float(np.median(least[at]))!r}",
                  file=sys.stderr, flush=True)
    return out, (out_c if control else None)
