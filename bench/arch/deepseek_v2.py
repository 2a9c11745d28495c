"""DeepSeek-V2 (`model_type` "deepseek_v2"), as one chip of an
expert-parallel deployment serves it: multi-head latent attention with an
uncompressed query and YaRN rope, `first_k_dense_replace` dense SiLU-gated
layers, then DeepSeekMoE layers whose router scores every published
expert and takes its top-k, of which this chip holds
`n_routed_experts` (from `expert_parallel.first_held_expert`), plus the
shared experts; an untied head. Tenants' Hadamard adapters sit on the
attention block's output.

Two groups: the dense layers, then the MoE layers. The program computes
MLA in its absorbed form at decode (the latent cache read as one KV head)
and expanded at prefill; the reference (`bench/refs/deepseek_v2.py`) is
the published, non-absorbed form.
"""
from __future__ import annotations

from bench import weights
from bench.common import BenchError
from bench.refs.deepseek_v2 import served_gaps  # noqa: F401  (the reference)

ACTIVATIONS = {"silu": "silu"}  # the file's name -> the program's
BYTES = {"bfloat16": 2, "float32": 4}
# the published routing the program implements, and nothing else
ROUTING = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
           "topk_group": 1, "moe_layer_freq": 1, "routed_scaling_factor": 1,
           "q_lora_rank": None, "attention_bias": False}


def groups(conf: dict) -> tuple:
    """Depths of the dense and the MoE group."""
    k = conf["first_k_dense_replace"]
    return k, conf["num_hidden_layers"] - k


def program_cfg(conf: dict):
    """The program's ModelCfg: the repo's arch entry with every size the
    file states put in, the held share of the experts, and the Hadamard
    adapter attached."""
    from repro.common.types import Group, MoECfg, Slot
    from repro.configs import get
    from repro.core import peft
    try:
        from repro.common.types import MLACfg, YarnCfg
    except ImportError as e:
        raise BenchError(f"the program under test has no latent attention "
                         f"(repro.common.types.MLACfg): {e}") from e

    for key, want in ROUTING.items():
        if conf[key] != want:
            raise ValueError(f"{key}={conf[key]!r}: the program implements "
                             f"{want!r} only")
    ep, y = conf["expert_parallel"], conf["rope_scaling"]
    dense, sparse = groups(conf)
    cfg = get(conf["arch"]).replace(
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["v_head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        groups=(Group((Slot("attn"),), dense),
                Group((Slot("attn", moe=True),), sparse)),
        moe=MoECfg(n_experts=ep["published_n_routed_experts"],
                   top_k=conf["num_experts_per_tok"],
                   d_expert=conf["moe_intermediate_size"],
                   n_shared=conf["n_shared_experts"], capacity_factor=None,
                   normalize_weights=conf["norm_topk_prob"],
                   held=(ep["first_held_expert"], conf["n_routed_experts"])),
        mla=MLACfg(kv_rank=conf["kv_lora_rank"],
                   nope_dim=conf["qk_nope_head_dim"],
                   rope_dim=conf["qk_rope_head_dim"],
                   v_dim=conf["v_head_dim"]),
        rope_scaling=YarnCfg(factor=y["factor"],
                             original_max=y["original_max_position_embeddings"],
                             beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
                             mscale=y["mscale"],
                             mscale_all_dim=y["mscale_all_dim"]),
        act=ACTIVATIONS[conf["hidden_act"]],
        param_dtype=conf["dtype"]["param"],
        compute_dtype=conf["dtype"]["compute"],
        norm_eps=conf["rms_norm_eps"], rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"])
    return peft.attach(cfg, peft.strategy("hadamard"))


def layout(conf: dict, tenants: int) -> dict:
    """path -> (shape, dtype, init) for the model's weights, this chip's
    experts and a bank of `tenants` adapters, in the groups of
    `program_cfg`."""
    d, V = conf["hidden_size"], conf["vocab_size"]
    H, R = conf["num_attention_heads"], conf["kv_lora_rank"]
    nope, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                    conf["v_head_dim"])
    E, n = conf["expert_parallel"]["published_n_routed_experts"], \
        conf["n_routed_experts"]
    f, sf = conf["moe_intermediate_size"], \
        conf["n_shared_experts"] * conf["moe_intermediate_size"]
    ff = conf["intermediate_size"]
    p, a = conf["dtype"]["param"], conf["dtype"]["adapter"]
    out = {
        "embed/table": ((V, d), p, "normal"),
        "final_norm/scale": ((d,), p, "ones"),
        "lm_head/kernel": ((d, V), p, "normal"),
    }
    for g, L in enumerate(groups(conf)):
        S = weights.stack(g)
        out.update({
            S + "attn_norm/scale": ((L, d), p, "ones"),
            S + "ffn_norm/scale": ((L, d), p, "ones"),
            S + "attn/wq": ((L, d, H * (nope + dr)), p, "normal"),
            S + "attn/wkv_a": ((L, d, R + dr), p, "normal"),
            S + "attn/kv_norm": ((L, R), p, "ones"),
            S + "attn/wkv_b": ((L, R, H * (nope + dv)), p, "normal"),
            S + "attn/wo": ((L, H * dv, d), p, "normal"),
            S + "adapter/w": ((L, tenants, d), a, "tenant_w"),
            S + "adapter/b": ((L, tenants, d), a, "tenant_b"),
        })
        if g == 0:
            out.update({
                S + "mlp/wi": ((L, d, ff), p, "normal"),   # gate (under silu)
                S + "mlp/wg": ((L, d, ff), p, "normal"),   # up
                S + "mlp/wo": ((L, ff, d), p, "normal"),   # down
            })
        else:
            out.update({
                S + "moe/router": ((L, d, E), "float32", "normal"),
                S + "moe/wi": ((L, n, d, f), p, "normal"),
                S + "moe/wg": ((L, n, d, f), p, "normal"),
                S + "moe/wo": ((L, n, f, d), p, "normal"),
                S + "moe/shared_wi": ((L, d, sf), p, "normal"),
                S + "moe/shared_wg": ((L, d, sf), p, "normal"),
                S + "moe/shared_wo": ((L, sf, d), p, "normal"),
            })
    return out


class Counts:
    """Operations and bytes that the algorithm needs, from shapes alone.

    A step reads every weight this chip holds once (its held experts, the
    shared experts, the router, the head; the embedding table only by the
    rows it gathers, which are not counted), and each active row's live
    latent cache below its position (kv_rank + rope_dim values per token
    per layer), and writes one latent per row; no `max_len` padding,
    inactive rows or prompt-bucket padding is charged. Attention is
    counted in the form each path computes: absorbed at decode (scores
    over kv_rank + rope, values over kv_rank, one shared KV head for
    every query head; W_UK and W_UV applied once per token each, which
    is the up-projection's weights once), expanded at prefill (per-head
    keys of nope + rope and values of v_dim, the latent expanded once per
    token). Routed experts are credited at top_k * held / published
    experts per token (6 * 8 / 64 = 0.75): the expectation under uniform
    routing, not what a given batch routed. Multiply-adds count two;
    norms, rope, softmax, the router's top-k and the adapter are left
    out."""

    def __init__(self, conf: dict):
        self.d, self.V = conf["hidden_size"], conf["vocab_size"]
        self.H, self.R = conf["num_attention_heads"], conf["kv_lora_rank"]
        self.nope, self.dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        self.dv = conf["v_head_dim"]
        self.dense, self.sparse = groups(conf)
        self.L = self.dense + self.sparse
        self.E = conf["expert_parallel"]["published_n_routed_experts"]
        self.held, self.k = conf["n_routed_experts"], conf["num_experts_per_tok"]
        self.wbytes = BYTES[conf["dtype"]["param"]]
        self.kvbytes = BYTES[conf["dtype"]["compute"]]
        d, H = self.d, self.H
        self.mla_params = d * H * (self.nope + self.dr) \
            + d * (self.R + self.dr) + self.R * H * (self.nope + self.dv) \
            + H * self.dv * d
        self.dense_params = 3 * d * conf["intermediate_size"]
        self.expert_params = 3 * d * conf["moe_intermediate_size"]
        self.shared_params = conf["n_shared_experts"] * self.expert_params
        self.router_params = d * self.E
        self.head_params = d * self.V
        self.embed_params = self.V * d

    @property
    def params(self) -> int:
        """Every weight this chip holds (norms and adapters left out)."""
        return self.embed_params + self.head_params \
            + self.L * self.mla_params + self.dense * self.dense_params \
            + self.sparse * (self.held * self.expert_params
                             + self.shared_params + self.router_params)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.L * (self.R + self.dr) * self.kvbytes

    @property
    def weight_bytes(self) -> int:
        """Every weight but the embedding table, read once per step (the
        router is float32)."""
        router = self.sparse * self.router_params
        return (self.params - self.embed_params - router) * self.wbytes \
            + router * 4

    def token_flops(self) -> int:
        """Matmul FLOPs of one token through every layer and the head,
        attention scores and values left out."""
        routed = self.k * self.held * self.expert_params / self.E
        return int(2 * (self.L * self.mla_params
                        + self.dense * self.dense_params
                        + self.sparse * (routed + self.shared_params
                                         + self.router_params)
                        + self.head_params))

    def decode_token_flops(self, pos: int) -> int:
        """One decode step of one row writing position `pos`: it attends,
        absorbed, over pos + 1 latents in every layer."""
        keys = pos + 1
        return self.token_flops() \
            + 2 * keys * self.H * (2 * self.R + self.dr) * self.L

    def prefill_flops(self, S: int) -> int:
        """A causal prefill of S prompt tokens, expanded, logits for the
        last only."""
        causal_keys = S * (S + 1) // 2
        head = 2 * self.head_params
        return S * (self.token_flops() - head) + head \
            + 2 * causal_keys * self.H * (self.nope + self.dr + self.dv) \
            * self.L

    def decode_step(self, positions) -> tuple:
        """(flops, bytes) of one fused decode step over the active rows,
        each writing at its own position."""
        flops = sum(self.decode_token_flops(p) for p in positions)
        kv = self.kv_bytes_per_token
        nbytes = self.weight_bytes + sum(p * kv for p in positions) \
            + len(positions) * kv
        return flops, nbytes

    def prefill(self, S: int) -> tuple:
        """(flops, bytes) of one prefill: weights once, the latents it
        writes."""
        return self.prefill_flops(S), self.weight_bytes \
            + S * self.kv_bytes_per_token
