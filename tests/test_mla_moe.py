"""DeepSeek-V2's latent attention and expert-parallel MoE against the plain
float32 reference (`bench/refs/deepseek_v2.py`) at a tiny size on the CPU:
served logits through the paged scheduler, YaRN against the published
formula, the experts' shares summing to the uncut layer, no drops under
skew, and `attend` with a value width unlike the key width."""
import dataclasses
import json
import math
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.append(str(REPO))  # the harness is the package `bench` there

from bench import common, weights  # noqa: E402
from bench.refs import deepseek_v2 as ref  # noqa: E402
from repro.common.types import MoECfg, YarnCfg  # noqa: E402
from repro.models import flash  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.layers import yarn_freqs, yarn_mscale  # noqa: E402
from repro.models.mla import softmax_scale  # noqa: E402
from repro.models.moe import moe_apply  # noqa: E402

TENANTS = 3
KEY = jax.random.PRNGKey(0)


def tiny_conf(**kw) -> dict:
    """The configuration file at a tiny size, float32, 16 published experts
    of which 4 held (from expert 4), top-4."""
    conf = json.loads(
        (REPO / "bench/configs/deepseek-v2-lite-ep8.json").read_text())
    conf.update(hidden_size=64, intermediate_size=96, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, moe_intermediate_size=24, n_shared_experts=2,
                num_experts_per_tok=4, n_routed_experts=4, vocab_size=256,
                initializer_range=0.1,
                dtype={"param": "float32", "compute": "float32",
                       "adapter": "float32"},
                expert_parallel={"chips_per_layer": 4,
                                 "published_n_routed_experts": 16,
                                 "first_held_expert": 4})
    conf.update(kw)
    return conf


def arch():
    return common.arch(tiny_conf())


def program(conf):
    return arch().program_cfg(conf).replace(q_chunk=16, kv_chunk=16)


# ---------------------------------------------------------------------------
# served logits: prefill, then paged decode through the scheduler
# ---------------------------------------------------------------------------


def test_paged_serving_matches_the_reference_by_logits():
    """Three requests on their own tenants, prompts bucketed to 16 on pages
    of 8, each prefilled then decoded 10 tokens through `PagedScheduler`:
    the program's logits at every served position against the reference's
    full forward over prompt + served tokens. Both run float32 (the
    reference at `highest`); they differ by the order of operations only
    (absorbed against expanded attention, grouped against dense experts),
    so the logits (of size ~1) agree to 2e-4."""
    from repro.serving import MultiTaskEngine, Request, ServingConfig, make_scheduler

    conf = tiny_conf()
    layout = arch().layout(conf, TENANTS)
    cfg = program(conf)
    weights.check_layout(layout, weights.program_shapes(cfg, TENANTS))
    flat = weights.flatten(weights.make(KEY, layout, conf["initializer_range"]))
    tasks = [weights.nest({p: (v[:, t] if "/adapter/" in p else v)
                           for p, v in flat.items()}) for t in range(TENANTS)]
    engine = MultiTaskEngine(cfg, tasks)
    sched = make_scheduler(engine, ServingConfig(
        paged=True, page_size=8, num_slots=2, max_len=64, prefill_bucket=16,
        num_blocks=32))

    seen = []  # (request id, position, logits) of every served token
    prefill, decode = engine.prefill, engine.paged_decode_step

    def on_prefill(tokens, cache_len, task_ids=None, last_pos=None):
        out = prefill(tokens, cache_len, task_ids=task_ids, last_pos=last_pos)
        seen.append((len([s for s in seen if s[1] == "prefill"]), "prefill",
                     np.asarray(out[0])[0, -1]))
        return out

    def on_decode(pool, tok, pos, tables, task_ids=None):
        logits, pool = decode(pool, tok, pos, tables, task_ids=task_ids)
        for i, st in enumerate(sched.slots):
            if st is not None:
                seen.append((st.request_id, int(pos[i]),
                             np.asarray(logits)[i, -1]))
        return logits, pool

    engine.prefill, engine.paged_decode_step = on_prefill, on_decode
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, conf["vocab_size"], n, dtype=np.int32)
               for n in (11, 16, 5)]
    reqs = [Request(prompt=p, max_new_tokens=10, task_id=t)
            for t, p in enumerate(prompts)]
    done = {c.request_id: np.asarray(c.tokens) for c in sched.run(reqs)[0]}
    assert sorted(done) == [0, 1, 2]

    W = weights.flatten(weights.make(KEY, layout, conf["initializer_range"]))
    first = conf["expert_parallel"]["first_held_expert"]
    n_checked = 0
    with jax.default_matmul_precision("highest"):
        for rid, p in enumerate(prompts):
            seq = np.concatenate([p, done[rid]])
            h = ref.forward(W, seq[None], np.asarray([rid]), conf, first)
            want = np.asarray(ref.logits(W, h[0], conf))
            for who, at, got in seen:
                if who == rid and at != "prefill" and at < len(seq) - 1:
                    np.testing.assert_allclose(got, want[at], atol=2e-4)
                    n_checked += 1
            got = next(g for who, at, g in seen
                       if at == "prefill" and who == rid)
            np.testing.assert_allclose(got, want[len(p) - 1], atol=2e-4)
    assert n_checked >= 3 * 8


def test_paged_decode_step_carries_the_mla_and_moe_scopes():
    conf = tiny_conf()
    cfg = program(conf)
    params = M.init_params(KEY, cfg)
    pool = M.init_paged_pool(cfg, 9, 4)
    text = jax.jit(lambda p, pool, t, pos, tbl: M.decode_lm_paged(
        p, cfg, pool, t, pos, tbl)).lower(
        params, pool, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 5), jnp.int32)
    ).as_text(debug_info=True)
    for name in ("repro.mla_absorb", "repro.moe_route", "repro.moe_experts",
                 "repro.moe_shared", "repro.kv_write", "repro.kv_gather",
                 "repro.attn_core"):
        assert re.search(re.escape(name) + r"\)*/", text), name
    # the pool leaf is the latent, 32 + 8 values padded to 128 lanes
    leaf = pool["g0"]["slot0"]["attn"]
    assert list(leaf) == ["latent"] and leaf["latent"].shape == (1, 9, 4, 128)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def published_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
                   mscale_all_dim):
    """DeepseekV2YarnRotaryEmbedding and the attention's softmax scale as
    published, line for line in numpy."""
    def find_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    def get_mscale(scale=1, m=1):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    cos_scale = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    m = get_mscale(factor, mscale_all_dim)
    return inv_freq, cos_scale, m * m


def test_yarn_matches_the_published_formula():
    full = json.loads(
        (REPO / "bench/configs/deepseek-v2-lite-ep8.json").read_text())
    y = full["rope_scaling"]
    inv, cs, m2 = published_yarn(64, 10000, y["factor"],
                                 y["original_max_position_embeddings"],
                                 y["beta_fast"], y["beta_slow"], y["mscale"],
                                 y["mscale_all_dim"])
    cfg = common.arch(full).program_cfg(full)
    np.testing.assert_allclose(
        np.asarray(yarn_freqs(64, 10000.0, cfg.rope_scaling)), inv, rtol=1e-6)
    assert yarn_mscale(40, 0.707) == pytest.approx(1 + 0.1 * 0.707 * math.log(40))
    assert yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert cs == 1.0  # mscale == mscale_all_dim: cos and sin unscaled
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m2)
    # the reference's transcription agrees too
    r_inv, r_cs, r_scale = ref.yarn(full)
    np.testing.assert_allclose(r_inv, inv, rtol=1e-6)
    assert (r_cs, r_scale) == pytest.approx((cs, 192 ** -0.5 * m2))
    # a cos/sin scale where mscale differs from mscale_all_dim
    inv2, cs2, _ = published_yarn(8, 10000, 4, 64, 32, 1, 1.0, 0.5)
    y2 = YarnCfg(factor=4, original_max=64, mscale=1.0, mscale_all_dim=0.5)
    np.testing.assert_allclose(np.asarray(yarn_freqs(8, 10000.0, y2)), inv2,
                               rtol=1e-6)
    assert cs2 == pytest.approx(yarn_mscale(4, 1.0) / yarn_mscale(4, 0.5))


# ---------------------------------------------------------------------------
# the expert layer: shares, and no drops
# ---------------------------------------------------------------------------


def _moe_world(held=None, E=16, k=4):
    conf = tiny_conf()
    cfg = program(conf).replace(moe=MoECfg(
        n_experts=E, top_k=k, d_expert=24, n_shared=2, capacity_factor=None,
        normalize_weights=False, held=held))
    ks = jax.random.split(KEY, 8)
    d, f = conf["hidden_size"], 24

    def n(i, *shape):
        return 0.1 * jax.random.normal(ks[i], shape, jnp.float32)

    p = {"router": n(0, d, E), "wi": n(1, E, d, f), "wg": n(2, E, d, f),
         "wo": n(3, E, f, d), "shared_wi": n(4, d, 2 * f),
         "shared_wg": n(5, d, 2 * f), "shared_wo": n(6, 2 * f, d)}
    if held is not None:  # the held experts' weights only
        p.update({w: p[w][held[0]:held[0] + held[1]] for w in ("wi", "wg", "wo")})
    x = jax.random.normal(ks[7], (3, 5, d), jnp.float32)
    return conf, cfg, p, x


def test_eight_shares_sum_to_the_uncut_layer():
    """Eight chips of two experts each: their routed parts, with the shared
    experts counted once (on the first), give the uncut layer, the
    program's and the reference's alike (float32; 1e-5 covers the order of
    summation)."""
    conf, cfg, p, x = _moe_world()
    whole, _ = moe_apply(p, cfg, x)
    total = 0.0
    for c in range(8):
        share = {k: (v[2 * c:2 * c + 2] if k in ("wi", "wg", "wo") else v)
                 for k, v in p.items()
                 if c == 0 or not k.startswith("shared")}
        held = dataclasses.replace(cfg.moe, held=(2 * c, 2))
        total = total + moe_apply(share, cfg.replace(moe=held), x)[0]
    np.testing.assert_allclose(total, whole, atol=1e-5)
    w = {f"moe/{k}": v for k, v in p.items()}
    uncut = dict(conf, n_routed_experts=16, num_experts_per_tok=4)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(w, x, uncut, 0)
    np.testing.assert_allclose(whole, want, atol=1e-5)


def test_a_skewed_batch_drops_no_token():
    """Every token puts the same expert first (one router column dominates
    for positive inputs): each row's output equals that row served alone,
    where the capacity-factor dispatch, given the same skew, drops."""
    conf, cfg, p, x = _moe_world(held=(0, 4))
    x = jnp.abs(x)
    p = dict(p, router=p["router"].at[:, 1].set(5.0))
    probs = jax.nn.softmax(x.reshape(-1, x.shape[-1]) @ p["router"], -1)
    assert bool(jnp.all(jnp.argmax(probs, -1) == 1))
    y, _ = moe_apply(p, cfg, x)
    for b in range(x.shape[0]):
        for s in range(x.shape[1]):
            alone, _ = moe_apply(p, cfg, x[b:b + 1, s:s + 1])
            np.testing.assert_allclose(y[b, s], alone[0, 0], atol=1e-5)
    capped = cfg.replace(moe=MoECfg(n_experts=4, top_k=2, d_expert=24,
                                    n_shared=2, normalize_weights=False))
    p = dict(p, router=p["router"][:, :4])
    yc, _ = moe_apply(p, capped, x)
    alone, _ = moe_apply(p, capped, x[-1:, -1:])
    assert not np.allclose(yc[-1, -1], alone[0, 0], atol=1e-3)


# ---------------------------------------------------------------------------
# attend with a value width unlike the key width
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("Dv", [8, 24])
def test_attend_takes_a_value_width_unlike_the_key_width(Dv):
    """Values of width Dv, whose first columns are the equal-width case's
    values: the output's first columns are that case's result, forward
    and backward (per-row positions and lengths, as paged decode has)."""
    ks = jax.random.split(KEY, 4)
    B, Sq, Skv, KH, G, D = 2, 3, 20, 1, 4, 16
    q = jax.random.normal(ks[0], (B, Sq, KH, G, D))
    k = jax.random.normal(ks[1], (B, Skv, KH, D))
    v = jax.random.normal(ks[2], (B, Skv, KH, max(Dv, D)))
    q_pos = jnp.asarray([[10, 11, 12], [15, 16, 17]])
    kw = dict(q_pos=q_pos, kv_pos=jnp.arange(Skv), kv_len=jnp.asarray([13, 18]),
              q_chunk=2, kv_chunk=8)
    same = flash.attend(q, k, v[..., :D], **kw)
    wide = flash.attend(q, k, v[..., :Dv], **kw)
    n = min(Dv, D)
    np.testing.assert_allclose(wide[..., :n], same[..., :n], atol=1e-5)

    def loss(q, k, v):
        return jnp.sum(flash.attend(q, k, v, **kw)[..., :n] ** 2)

    g_same = jax.grad(loss, (0, 1))(q, k, v[..., :D])
    g_wide = jax.grad(loss, (0, 1))(q, k, v[..., :Dv])
    for a, b in zip(g_same, g_wide):
        np.testing.assert_allclose(a, b, atol=1e-4)


# ---------------------------------------------------------------------------
# paths latent attention does not take
# ---------------------------------------------------------------------------


def test_latent_attention_refuses_quantized_kv_and_spec_verify():
    from repro.serving import MultiTaskEngine, ServingConfig, make_scheduler

    conf = tiny_conf()
    cfg = program(conf)
    with pytest.raises(ValueError, match="kv_quant='int8' is not supported "
                                         "for latent attention"):
        M.init_paged_pool(cfg, 9, 4, quant="int8")
    params = M.init_params(KEY, cfg)
    with pytest.raises(ValueError, match="speculative verify"):
        M.verify_lm_paged(params, cfg, M.init_paged_pool(cfg, 9, 4),
                          jnp.zeros((1, 3), jnp.int32),
                          jnp.zeros((1,), jnp.int32),
                          jnp.zeros((1, 4), jnp.int32))
    engine = MultiTaskEngine(cfg, [params])
    with pytest.raises(ValueError, match="not supported for latent"):
        make_scheduler(engine, ServingConfig(paged=True, page_size=8,
                                             num_slots=2, max_len=32,
                                             num_blocks=16, kv_quant="fp8"))
    with pytest.raises(ValueError, match="spec_k"):
        make_scheduler(engine, ServingConfig(paged=True, page_size=8,
                                             num_slots=2, max_len=32,
                                             num_blocks=16, spec_k=2))
