"""Model-family behaviour: forward shapes, causality, decode consistency,
adapter identity, flash-vs-dense equivalence inside the full model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import record_kv_tiles, stacked_groups, tiny_cfg
from repro.common.types import AdapterCfg, Group, MoECfg, Slot
from repro.models import model as M

KEY = jax.random.PRNGKey(0)


def test_decoder_causality():
    """Changing a future token must not change past logits."""
    cfg = tiny_cfg()
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (1, 12), 0, 97)
    l1, _ = M.forward_lm(p, cfg, toks)
    toks2 = toks.at[0, 8].set((toks[0, 8] + 1) % 97)
    l2, _ = M.forward_lm(p, cfg, toks2)
    np.testing.assert_allclose(np.asarray(l1[0, :8]), np.asarray(l2[0, :8]),
                               atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 8:]), np.asarray(l2[0, 8:]))


def test_encoder_not_causal():
    cfg = tiny_cfg(family="encoder", ln_placement="post", pos="learned",
                   n_segment_types=2, norm="layernorm", gated_mlp=False,
                   act="gelu", attn_bias=True, mlp_bias=True, pooler=True)
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (1, 12), 0, 97)
    _, _, h1 = M.forward_encoder(p, cfg, toks, jnp.zeros_like(toks))
    toks2 = toks.at[0, 11].set((toks[0, 11] + 1) % 97)
    _, _, h2 = M.forward_encoder(p, cfg, toks2, jnp.zeros_like(toks))
    # bidirectional: early positions DO change
    assert not np.allclose(np.asarray(h1[0, 0]), np.asarray(h2[0, 0]))


def test_hadamard_identity_init_matches_no_adapter():
    """w=1/b=0 adapters leave the function unchanged (paper §3.1)."""
    cfg_no = tiny_cfg(adapter=AdapterCfg(kind="none"))
    cfg_ad = tiny_cfg(adapter=AdapterCfg(kind="hadamard"))
    p_ad = M.init_params(KEY, cfg_ad)
    from repro.common import tree as tu

    # strip adapters to build the no-adapter tree with identical weights
    p_no = {k: v for k, v in p_ad.items()}
    import copy

    def strip(t):
        if isinstance(t, dict):
            return {k: strip(v) for k, v in t.items() if k != "adapter"}
        return t

    p_no = strip(p_ad)
    toks = jax.random.randint(KEY, (2, 10), 0, 97)
    l_ad, _ = M.forward_lm(p_ad, cfg_ad, toks)
    l_no, _ = M.forward_lm(p_no, cfg_no, toks)
    np.testing.assert_allclose(np.asarray(l_ad), np.asarray(l_no), atol=1e-6)


@pytest.mark.parametrize("position", ["attn_out", "attn_concat"])
def test_adapter_positions_affect_output(position):
    cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard", position=position))
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (1, 8), 0, 97)
    base, _ = M.forward_lm(p, cfg, toks)
    p2 = jax.tree.map(lambda x: x, p)
    ad = p2["blocks"]["g0"]["slot0"]["adapter"]
    ad["b"] = ad["b"] + 0.3
    pert, _ = M.forward_lm(p2, cfg, toks)
    assert not np.allclose(np.asarray(base), np.asarray(pert))


@pytest.mark.parametrize("kind", ["lora", "ia3", "houlsby"])
def test_baseline_adapters_run(kind):
    cfg = tiny_cfg(adapter=AdapterCfg(kind=kind))
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 8), 0, 97)
    logits, _ = M.forward_lm(p, cfg, toks)
    assert logits.shape == (2, 8, 97)
    assert not jnp.isnan(logits).any()


def test_moe_routes_and_balances():
    cfg = tiny_cfg(groups=(Group((Slot("attn", moe=True),), 2),),
                   moe=MoECfg(n_experts=4, top_k=2, d_expert=32, n_shared=1))
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, 97)
    logits, aux = M.forward_lm(p, cfg, toks)
    assert not jnp.isnan(logits).any()
    assert float(aux) > 0  # load-balance loss present


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor >= 1 and uniform routing, most tokens survive;
    the MoE output must differ from shared-experts-only (routing matters)."""
    from repro.models.moe import moe_apply

    cfg = tiny_cfg(moe=MoECfg(n_experts=4, top_k=1, d_expert=16, n_shared=0,
                              capacity_factor=2.0))
    from repro.models.moe import moe_init

    p = moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 32, 64))
    y, aux = moe_apply(p, cfg, x)
    assert not jnp.isnan(y).any()
    assert float(jnp.abs(y).sum()) > 0


@pytest.mark.parametrize("family_cfg", ["rwkv", "rec", "hybrid"])
def test_recurrent_families_decode_match_forward(family_cfg):
    if family_cfg == "rwkv":
        cfg = tiny_cfg(groups=(Group((Slot("rwkv"),), 2),), rwkv_head_dim=16,
                       pos="none", norm="layernorm")
    elif family_cfg == "rec":
        cfg = tiny_cfg(groups=(Group((Slot("rec"),), 2),), lru_width=64)
    else:
        cfg = tiny_cfg(groups=(Group((Slot("rec"), Slot("rec"),
                                      Slot("attn", window=8)), 2),),
                       lru_width=64)
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, 97)
    full, _ = M.forward_lm(p, cfg, toks)
    _, caches = M.prefill_lm(p, cfg, toks[:, :15], cache_len=16)
    dec, _ = M.decode_lm(p, cfg, caches, toks[:, 15:16], jnp.int32(15))
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, 15]),
                               atol=5e-4)


@pytest.mark.parametrize("group", stacked_groups())
def test_multi_step_decode_matches_forward(group):
    cfg = tiny_cfg(groups=(group,))
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, 97)
    full, _ = M.forward_lm(p, cfg, toks)
    _, caches = M.prefill_lm(p, cfg, toks[:, :12], cache_len=16)
    for t in range(12, 16):
        dec, caches = M.decode_lm(p, cfg, caches, toks[:, t : t + 1],
                                  jnp.int32(t))
        np.testing.assert_allclose(np.asarray(dec[:, 0]),
                                   np.asarray(full[:, t]), atol=5e-4)


def test_encdec_decode_matches_forward():
    cfg = tiny_cfg(family="encdec", pos="learned", norm="layernorm",
                   gated_mlp=False, act="gelu", attn_bias=True,
                   groups=(Group((Slot("attn", cross_attn=True),), 2),),
                   enc_groups=(Group((Slot("attn"),), 2),), n_audio_frames=8)
    p = M.init_params(KEY, cfg)
    frames = jax.random.normal(KEY, (2, 8, 64))
    toks = jax.random.randint(KEY, (2, 12), 0, 97)
    full, _ = M.forward_encdec(p, cfg, frames, toks)
    _, caches = M.prefill_encdec(p, cfg, frames, toks[:, :11], cache_len=12)
    dec, _ = M.decode_encdec(p, cfg, caches, toks[:, 11:12], jnp.int32(11))
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, 11]),
                               atol=5e-4)


def test_vlm_concatenates_patches():
    cfg = tiny_cfg(family="vlm", n_image_tokens=4)
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 8), 0, 97)
    patches = jax.random.normal(KEY, (2, 4, 64))
    logits, _ = M.forward_lm(p, cfg, toks, patches=patches)
    assert logits.shape == (2, 12, 97)  # 4 image + 8 text positions
    # changing a patch changes text logits (cross-modal attention works)
    patches2 = patches.at[0, 0].add(1.0)
    l2, _ = M.forward_lm(p, cfg, toks, patches=patches2)
    assert not np.allclose(np.asarray(logits[0, 4:]), np.asarray(l2[0, 4:]))


def test_windowed_attention_limits_range():
    """With window w, logits at position t must ignore tokens < t - w."""
    cfg = tiny_cfg(groups=(Group((Slot("attn", window=4),), 2),))
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (1, 16), 0, 97)
    l1, _ = M.forward_lm(p, cfg, toks)
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % 97)  # far outside window
    l2, _ = M.forward_lm(p, cfg, toks2)
    np.testing.assert_allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]),
                               atol=1e-5)


def _decode_logits(p, cfg, toks, prompt: int, cache_len: int):
    """Prefill `prompt` tokens into a `cache_len` cache, then decode the
    rest of `toks` one at a time (Sq = 1): every step's logits."""
    _, caches = M.prefill_lm(p, cfg, toks[:, :prompt], cache_len=cache_len)
    out = []
    for t in range(prompt, toks.shape[1]):
        lg, caches = M.decode_lm(p, cfg, caches, toks[:, t:t + 1], jnp.int32(t))
        out.append(lg[:, 0])
    return jnp.stack(out, 1)


@pytest.mark.parametrize("step", ["forward", "decode"])
def test_flash_chunking_invariance(step, monkeypatch):
    """Different chunk sizes produce identical logits. `decode` scores one
    query (Sq = 1) over a 40-position cache past the small config's
    2 x 8 tile budget, so it still runs the online softmax over several
    kv chunks; the large config's reference takes the view in one."""
    toks = jax.random.randint(KEY, (1, 24), 0, 97)
    small = tiny_cfg(q_chunk=8, kv_chunk=8)
    big = tiny_cfg(q_chunk=64, kv_chunk=64)
    if step == "decode":
        small = tiny_cfg(q_chunk=2, kv_chunk=8)
        tiles = record_kv_tiles(monkeypatch)
    p = M.init_params(KEY, small)
    if step == "forward":
        ls, _ = M.forward_lm(p, small, toks)
        lb, _ = M.forward_lm(p, big, toks)
    else:
        ls = _decode_logits(p, small, toks, 16, 40)
        assert {t for t in tiles if t[0][0] == 1} == {((1, 40), (1, 8))}
        tiles.clear()
        lb = _decode_logits(p, big, toks, 16, 40)
        assert {t for t in tiles if t[0][0] == 1} == {((1, 40), (1, 40))}
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lb), atol=2e-4)


@pytest.mark.parametrize("arch,Sq,Skv,want", [
    pytest.param("qwen3-0.6b", 1, 1536, (1, 1536), id="decode_1x1536"),
    pytest.param("qwen3-0.6b", 5, 1536, (5, 1536), id="verify_5x1536"),
    pytest.param("qwen3-0.6b", 256, 1536, (256, 1536), id="extend_256x1536"),
    pytest.param("qwen3-0.6b", 512, 1024, (512, 1024), id="prefill_512x1024"),
    pytest.param("qwen3-0.6b", 1024, 1024, (512, 1024),
                 id="prefill_1024x1024"),
    pytest.param("roberta-large", 128, 128, (128, 128), id="train_128x128"),
    pytest.param("deepseek-v2-lite", 1, 1536, (1, 1536), id="mla_decode"),
    # past the budget the keys go in kv_chunk pieces, as before
    pytest.param("qwen3-0.6b", 1, 600_000, (1, 1024), id="decode_past"),
    pytest.param("qwen3-0.6b", 512, 1536, (512, 1024), id="extend_past"),
    pytest.param("qwen3-0.6b", 1024, 4096, (512, 1024), id="prefill_past"),
])
def test_flash_kv_tile_choice(arch, Sq, Skv, want):
    """The flash tile under each configuration's q_chunk x kv_chunk
    budget: one kv chunk when a q tile's scores over every key fit it;
    prefill and train keep min(q_chunk, Sq) x min(kv_chunk, Skv)."""
    from repro.configs import get
    from repro.models.flash import _kv_tile

    cfg = get(arch)
    assert _kv_tile(Sq, Skv, cfg.q_chunk, cfg.kv_chunk) == want
    if Sq >= cfg.q_chunk or Skv <= cfg.kv_chunk:  # prefill and train
        assert want == (min(cfg.q_chunk, Sq), min(cfg.kv_chunk, Skv))
