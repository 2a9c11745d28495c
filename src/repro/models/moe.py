"""Mixture-of-experts FFN with sort-based capacity dispatch (GShard-style
groups + token dropping, MaxText-style argsort routing) plus always-on
shared experts (DeepSeek-MoE fine-grained layout).

A config with `capacity_factor=None` routes dropless instead
(`_dropless`): every (token, chosen expert) assignment that falls on an
expert this chip holds (`MoECfg.held`) is sorted by expert and run
through grouped matmuls (`jax.lax.ragged_dot`), so no token is dropped
and a row's output never depends on what else shares its batch. That is
the expert-parallel layer: the router scores every expert, and the
chip adds its held experts' part of the result; on one chip there is no
exchange with the chips that hold the rest.

Sharding story (what makes this scale):
  * tokens are split into G data-aligned groups: (G, T/G, d) with
    P(dp, None, None) - every device routes ITS tokens locally; the
    data-dependent argsort/scatter never crosses shards (a naive global
    sort makes XLA replicate the whole token array: ~26 GB/device at
    1M tokens - measured before this layout).
  * expert-stacked weights (E, d, f) -> P('model', ...): expert parallelism
    is just a sharding rule; the (G, E, cap, d) dispatch buffer crossing
    from dp-sharded groups to model-sharded experts is the all-to-all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.types import ModelCfg, MoECfg
from repro.dist.api import constrain, current_mesh, dp_axes
from repro.models.layers import act_fn, dense_init
from repro.obs.profile import scope


def moe_init(key, cfg: ModelCfg):
    m = cfg.moe
    ks = jax.random.split(key, 8)
    d, f, E = cfg.d_model, m.d_expert, m.held_range[1]
    init = lambda k, shape: (
        jax.random.truncated_normal(k, -2.0, 2.0, shape) * 0.02
    ).astype(cfg.pdtype)
    p = {
        "router": dense_init(ks[0], d, m.n_experts, jnp.float32),
        "wi": init(ks[1], (E, d, f)),
        "wo": init(ks[2], (E, f, d)),
    }
    if cfg.gated_mlp:
        p["wg"] = init(ks[3], (E, d, f))
    if m.n_shared:
        sf = m.n_shared * f
        p["shared_wi"] = dense_init(ks[4], d, sf, cfg.pdtype)
        p["shared_wo"] = dense_init(ks[5], sf, d, cfg.pdtype)
        if cfg.gated_mlp:
            p["shared_wg"] = dense_init(ks[6], d, sf, cfg.pdtype)
    return p


def _n_groups(T: int) -> int:
    """Routing groups = data-parallel shard count (1 when unmeshed)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    g = 1
    for a in dp_axes(mesh):
        g *= sizes.get(a, 1)
    while g > 1 and T % g != 0:
        g //= 2
    return max(g, 1)


def _route_group(xg, router, m: MoECfg, cap: int, cdt):
    """Per-group dispatch. xg: (Tg, d). Returns (buf, combine_info, probs)."""
    Tg, d = xg.shape
    E, k = m.n_experts, m.top_k

    logits = xg.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)  # (Tg, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    if m.normalize_weights:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    flat_e = expert_idx.reshape(-1)  # (Tg*k,)
    flat_t = jnp.repeat(jnp.arange(Tg), k)
    flat_g = gate_vals.reshape(-1)

    order = jnp.argsort(flat_e)
    e_sorted = flat_e[order]
    t_sorted = flat_t[order]
    g_sorted = flat_g[order]

    counts = jnp.bincount(flat_e, length=E)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(Tg * k) - offsets[e_sorted]
    keep = pos_in_e < cap
    dest = jnp.where(keep, e_sorted * cap + pos_in_e, E * cap)  # E*cap = drop

    buf = jnp.zeros((E * cap, d), cdt).at[dest].set(
        xg[t_sorted].astype(cdt), mode="drop")
    return buf.reshape(E, cap, d), (dest, keep, t_sorted, g_sorted), probs


def _combine_group(rows, info, Tg: int, cdt):
    """rows: (E*cap+..., d) expert outputs for one group."""
    dest, keep, t_sorted, g_sorted = info
    gathered = jnp.take(rows, jnp.where(keep, dest, rows.shape[0] - 1), axis=0,
                        mode="fill", fill_value=0)
    gathered = jnp.where(keep[:, None], gathered, 0)
    weighted = gathered * g_sorted[:, None].astype(cdt)
    d = rows.shape[-1]
    return jnp.zeros((Tg, d), cdt).at[t_sorted].add(weighted)


def _aux_loss(probs, m: MoECfg):
    """Switch-style load-balancing loss over every expert's probability."""
    T, E = probs.shape
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[jnp.argmax(probs, axis=-1)].add(1.0) / T
    return m.aux_loss_weight * E * jnp.sum(me * ce)


def _shared(p, cfg: ModelCfg, xf):
    cdt = cfg.cdtype
    hs = xf @ p["shared_wi"].astype(cdt)
    if cfg.gated_mlp:
        hs = act_fn(cfg.act)(hs) * (xf @ p["shared_wg"].astype(cdt))
    else:
        hs = act_fn(cfg.act)(hs)
    return hs @ p["shared_wo"].astype(cdt)


def _dropless(p, cfg: ModelCfg, x):
    """The held experts' part of every token's top-k mixture, plus the
    shared experts. Assignments to experts held elsewhere sort after the
    held ones, outside every group, and add nothing."""
    m: MoECfg = cfg.moe
    B, S, d = x.shape
    T, k = B * S, m.top_k
    first, n = m.held_range
    cdt = cfg.cdtype
    xf = x.reshape(T, d)
    with scope("repro.moe_route"):
        probs = jax.nn.softmax(xf.astype(jnp.float32) @ p["router"], axis=-1)
        gate, idx = jax.lax.top_k(probs, k)  # greedy top-k over all experts
        if m.normalize_weights:
            gate = gate / (gate.sum(-1, keepdims=True) + 1e-9)
        local = (idx - first).reshape(-1)
        mine = (local >= 0) & (local < n)
        group = jnp.where(mine, local, n)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
        rows = xf[order // k].astype(cdt)  # (T*k, d), by held expert
        aux = _aux_loss(probs, m)
    with scope("repro.moe_experts"):
        def grouped(a, w):
            return jax.lax.ragged_dot(a, w.astype(cdt), sizes,
                                      preferred_element_type=jnp.float32)

        h = act_fn(cfg.act)(grouped(rows, p["wi"]))
        if cfg.gated_mlp:
            h = h * grouped(rows, p["wg"])
        out = grouped(h.astype(cdt), p["wo"])
        w = gate.reshape(-1)[order]
        out = jnp.where(mine[order][:, None], out * w[:, None], 0.0)
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
        y = out[inv].reshape(T, k, d).sum(axis=1).astype(cdt)
    if "shared_wi" in p:
        with scope("repro.moe_shared"):
            y = y + _shared(p, cfg, xf.astype(cdt))
    return y.reshape(B, S, d), aux


def moe_apply(p, cfg: ModelCfg, x):
    """x: (B, S, d). Returns (y, aux_loss)."""
    m: MoECfg = cfg.moe
    if m.capacity_factor is None:
        return _dropless(p, cfg, x)
    if m.held is not None:
        raise ValueError("a held share of the experts routes dropless "
                         "(capacity_factor=None)")
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    cdt = cfg.cdtype

    G = _n_groups(T)
    Tg = T // G
    cap = int(max(1, -(-Tg * k * m.capacity_factor // E)))

    xg = constrain(x.reshape(G, Tg, d), "dp", None, None)

    buf, info, probs = jax.vmap(
        lambda g: _route_group(g, p["router"], m, cap, cdt))(xg)
    # (G, E, cap, d): groups stay on dp shards, experts go to model shards
    buf = constrain(buf, "dp", "model", None, None)

    # --- aux load-balancing loss (Switch-style, computed globally) ---
    aux = _aux_loss(probs.reshape(T, E), m)

    # --- expert FFN (batched einsum over groups x experts) ---
    h = jnp.einsum("gecd,edf->gecf", buf, p["wi"].astype(cdt))
    if cfg.gated_mlp:
        h = act_fn(cfg.act)(h) * jnp.einsum("gecd,edf->gecf", buf,
                                            p["wg"].astype(cdt))
    else:
        h = act_fn(cfg.act)(h)
    out_e = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(cdt))
    out_e = constrain(out_e, "dp", "model", None, None)

    rows = out_e.reshape(G, E * cap, d)
    # pad row E*cap acts as the drop sink during combine
    rows = jnp.concatenate([rows, jnp.zeros((G, 1, d), cdt)], axis=1)
    yg = jax.vmap(lambda r, i: _combine_group(r, i, Tg, cdt))(rows, info)
    y = constrain(yg, "dp", None, None).reshape(B, S, d)

    # --- shared (always-on) experts ---
    if "shared_wi" in p:
        y = y + _shared(p, cfg, x.reshape(T, d)).reshape(B, S, d)

    return y, aux
