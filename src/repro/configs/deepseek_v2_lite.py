"""deepseek-v2-lite [hf:deepseek-ai/DeepSeek-V2-Lite]: 27L d=2048 16H
vocab=102400, untied head. Multi-head latent attention with no query
compression (q: 16 x (128 nope + 64 rope); latent 512 + 64 rope key;
values 16 x 128), YaRN rope (factor 40 over 4096 positions, mscale
0.707). Layer 0 is a dense SiLU-gated MLP of width 10944; layers 1-26
route each token's top-6 of 64 experts (width 1408, softmax, greedy, no
renormalisation, routed scale 1) dropless, plus 2 shared experts."""
from repro.common.types import Group, MLACfg, ModelCfg, MoECfg, Slot, YarnCfg
from repro.configs.util import smoke_dims


def config() -> ModelCfg:
    return ModelCfg(
        name="deepseek-v2-lite",
        family="decoder",
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=128,  # the value width per head
        d_ff=10944,
        vocab_size=102400,
        groups=(
            Group((Slot("attn", moe=False),), 1),
            Group((Slot("attn", moe=True),), 26),
        ),
        moe=MoECfg(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                   capacity_factor=None, normalize_weights=False),
        mla=MLACfg(kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128),
        rope_scaling=YarnCfg(factor=40.0, original_max=4096, beta_fast=32.0,
                             beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
        norm="rmsnorm",
        act="silu",
        gated_mlp=True,
        pos="rope",
        rope_theta=10000.0,
        max_seq_len=163840,
        # decode reads the latent as one KV head, so a whole view's scores
        # (B x 16 heads x L, f32) are small: one kv chunk up to 4096
        # positions. The default 1024 pads a 1536-position view to 2048
        # and slices it in two on every decode step.
        kv_chunk=4096,
        shard_profile="tp",
    )


def smoke() -> ModelCfg:
    return smoke_dims(
        config(),
        n_kv_heads=4,
        groups=(
            Group((Slot("attn", moe=False),), 1),
            Group((Slot("attn", moe=True),), 2),
        ),
        moe=MoECfg(n_experts=8, top_k=2, d_expert=32, n_shared=1,
                   capacity_factor=None, normalize_weights=False),
        mla=MLACfg(kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16),
    )
