"""Qwen3 (`model_type` "qwen3"): a pre-norm GQA decoder with per-head q/k
RMSNorm, rotary embeddings, a SiLU-gated MLP and tied embeddings, serving
a bank of tenants' Hadamard adapters on the attention block's output.

The layer pattern is a tuple of group depths, every layer a dense
attention block: all layers in one group unless a caller passes another
pattern (a module that lays the same layers out in several groups wraps
`program_cfg` and `layout`; the reference follows the layout's groups).
"""
from __future__ import annotations

from bench import weights
from bench.refs.decoder import served_gaps  # noqa: F401  (the reference)

ACTIVATIONS = {"silu": "silu"}  # the file's name -> the program's
BYTES = {"bfloat16": 2, "float32": 4}


def program_cfg(conf: dict, groups=None):
    """The program's ModelCfg: the repo's arch entry with every size the
    file states put in, one group of `groups[i]` layers per entry, and the
    Hadamard adapter attached."""
    from repro.common.types import Group, Slot
    from repro.configs import get
    from repro.core import peft

    groups = groups or (conf["num_hidden_layers"],)
    cfg = get(conf["arch"]).replace(
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        groups=tuple(Group((Slot("attn"),), n) for n in groups),
        act=ACTIVATIONS[conf["hidden_act"]],
        param_dtype=conf["dtype"]["param"],
        compute_dtype=conf["dtype"]["compute"],
        norm_eps=conf["rms_norm_eps"], rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"])
    return peft.attach(cfg, peft.strategy("hadamard"))


def layout(conf: dict, tenants: int, groups=None) -> dict:
    """path -> (shape, dtype, init) for the decoder's weights and a bank of
    `tenants` adapters, in the groups of `program_cfg`."""
    d = conf["hidden_size"]
    H, KH = conf["num_attention_heads"], conf["num_key_value_heads"]
    Dh, ff, V = conf["head_dim"], conf["intermediate_size"], conf["vocab_size"]
    p = conf["dtype"]["param"]
    a = conf["dtype"]["adapter"]
    out = {
        "embed/table": ((V, d), p, "normal"),
        "final_norm/scale": ((d,), p, "ones"),
    }
    for g, L in enumerate(groups or (conf["num_hidden_layers"],)):
        S = weights.stack(g)
        out.update({
            S + "attn_norm/scale": ((L, d), p, "ones"),
            S + "ffn_norm/scale": ((L, d), p, "ones"),
            S + "attn/wq": ((L, d, H * Dh), p, "normal"),
            S + "attn/wk": ((L, d, KH * Dh), p, "normal"),
            S + "attn/wv": ((L, d, KH * Dh), p, "normal"),
            S + "attn/wo": ((L, H * Dh, d), p, "normal"),
            S + "attn/q_norm": ((L, Dh), p, "ones"),
            S + "attn/k_norm": ((L, Dh), p, "ones"),
            S + "mlp/wi": ((L, d, ff), p, "normal"),   # gate (under silu)
            S + "mlp/wg": ((L, d, ff), p, "normal"),   # up
            S + "mlp/wo": ((L, ff, d), p, "normal"),   # down
            S + "adapter/w": ((L, tenants, d), a, "tenant_w"),
            S + "adapter/b": ((L, tenants, d), a, "tenant_b"),
        })
    return out


class Counts:
    """Operations and bytes that the algorithm needs, from shapes alone.

    Counts credit the work a request needs, not what a program happens to
    touch: a decode step reads the weights once and each active row's
    live KV (positions below its own), and writes one new KV entry per
    row; it is not charged for cache padding up to `max_len`, for
    inactive rows, or for prompt-bucket padding. Multiply-adds count as
    two operations. Norms, RoPE, softmax and the adapter's elementwise
    affine are left out (under 0.1% of the matmul work at these widths).
    The lm head is of the vocabulary's width, tied or not."""

    def __init__(self, conf: dict):
        self.L = conf["num_hidden_layers"]
        self.d = conf["hidden_size"]
        self.H, self.KH = conf["num_attention_heads"], conf["num_key_value_heads"]
        self.Dh = conf["head_dim"]
        self.ff = conf["intermediate_size"]
        self.V = conf["vocab_size"]
        self.wbytes = BYTES[conf["dtype"]["param"]]
        self.kvbytes = BYTES[conf["dtype"]["compute"]]
        qd, kvd = self.H * self.Dh, self.KH * self.Dh
        # matmul weights of one layer: q, k, v, o, gate, up, down
        self.layer_params = self.d * (qd + 2 * kvd) + qd * self.d \
            + 3 * self.d * self.ff
        self.head_params = self.d * self.V

    @property
    def kv_bytes_per_token(self) -> int:
        return self.L * 2 * self.KH * self.Dh * self.kvbytes

    @property
    def weight_bytes(self) -> int:
        """Matmul weights plus the head, read once per step."""
        return (self.L * self.layer_params + self.head_params) * self.wbytes

    def attn_flops(self, q: int, keys: int) -> int:
        """QK^T and PV for q queries over `keys` keys, every layer."""
        return 4 * q * keys * self.H * self.Dh * self.L

    def decode_token_flops(self, pos: int) -> int:
        """One decode step of one row writing position `pos` (it attends
        over pos + 1 keys), with the head over the vocabulary."""
        return 2 * (self.L * self.layer_params + self.head_params) \
            + self.attn_flops(1, pos + 1)

    def prefill_flops(self, S: int) -> int:
        """A causal prefill of S prompt tokens, logits for the last only."""
        causal_keys = S * (S + 1) // 2
        return 2 * S * self.L * self.layer_params + 2 * self.head_params \
            + 4 * causal_keys * self.H * self.Dh * self.L

    def decode_step(self, positions) -> tuple:
        """(flops, bytes) of one fused decode step over the active rows,
        each writing at its own position."""
        flops = sum(self.decode_token_flops(p) for p in positions)
        kv = self.kv_bytes_per_token
        nbytes = self.weight_bytes + sum(p * kv for p in positions) \
            + len(positions) * kv
        return flops, nbytes

    def prefill(self, S: int) -> tuple:
        """(flops, bytes) of one prefill: weights once, the KV it writes."""
        return self.prefill_flops(S), self.weight_bytes \
            + S * self.kv_bytes_per_token
