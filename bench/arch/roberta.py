"""RoBERTa (`model_type` "roberta"): a post-LN BERT-style encoder
classifier with learned positions, a pooler and a two-class head,
trained through identity Hadamard adapters (the paper's stage 2: the
adapters and the FFN-output LayerNorm train, the backbone is frozen).
"""
from __future__ import annotations

from bench import weights
from bench.refs.encoder import train as train_reference  # noqa: F401

ACTIVATIONS = {"gelu_tanh": "gelu"}  # the file's name -> the program's


def program_cfg(conf: dict):
    """The program's ModelCfg: the repo's arch entry with every size the
    file states put in, all layers in one group, and the Hadamard adapter
    attached."""
    from repro.common.types import Group, Slot
    from repro.configs import get
    from repro.core import peft

    H = conf["num_attention_heads"]
    cfg = get(conf["arch"]).replace(
        d_model=conf["hidden_size"], n_heads=H, n_kv_heads=H,
        head_dim=conf["hidden_size"] // H, d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        groups=(Group((Slot("attn"),), conf["num_hidden_layers"]),),
        act=ACTIVATIONS[conf["hidden_act"]],
        param_dtype=conf["dtype"]["param"],
        compute_dtype=conf["dtype"]["compute"],
        norm_eps=conf["layer_norm_eps"],
        max_seq_len=conf["max_position_embeddings"],
        n_segment_types=conf["type_vocab_size"])
    return peft.attach(cfg, peft.strategy("hadamard"))


def layout(conf: dict) -> dict:
    """path -> (shape, dtype, init) for the encoder's weights, the pooler,
    the head and identity Hadamard adapters."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    ff, V = conf["intermediate_size"], conf["vocab_size"]
    P, T = conf["max_position_embeddings"], conf["type_vocab_size"]
    p = conf["dtype"]["param"]
    a = conf["dtype"]["adapter"]
    C = conf["num_labels"]
    S = weights.stack()
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
        S + "adapter/w": ((L, d), a, "ones"),
        S + "adapter/b": ((L, d), a, "zeros"),
    }
    for n in ("attn_norm", "ffn_norm"):
        out[S + n + "/scale"] = ((L, d), p, "ones")
        out[S + n + "/bias"] = ((L, d), p, "zeros")
    for n in ("wq", "wk", "wv", "wo"):
        out[S + "attn/" + n] = ((L, d, d), p, "normal")
    for n in ("bq", "bk", "bv", "bo"):
        out[S + "attn/" + n] = ((L, d), p, "zeros")
    out[S + "mlp/wi"] = ((L, d, ff), p, "normal")
    out[S + "mlp/wo"] = ((L, ff, d), p, "normal")
    out[S + "mlp/bi"] = ((L, ff), p, "zeros")
    out[S + "mlp/bo"] = ((L, d), p, "zeros")
    return out


class Counts:
    """Operations that the algorithm needs, from shapes alone, for the
    encoder trained with a frozen backbone and trainable elementwise
    leaves (adapter, ffn norm). Multiply-adds count as two operations."""

    def __init__(self, conf: dict):
        self.L = conf["num_hidden_layers"]
        self.d = conf["hidden_size"]
        self.ff = conf["intermediate_size"]
        self.layer_params = 4 * self.d * self.d + 2 * self.d * self.ff
        self.C = conf["num_labels"]

    def train_token_flops(self, seq: int) -> float:
        """Needed operations per token of one train step at sequence
        length `seq`: the forward (matmuls and attention over all seq
        keys), the backward's activation gradients only (the backbone is
        frozen: one matmul per weight, and dQ, dK, dV, dP in attention),
        and the trainable leaves' elementwise gradients. The pooler and
        head run on one token of each sequence."""
        mat = 2 * self.L * self.layer_params
        attn = 4 * seq * self.d * self.L
        head = 2 * (self.d * self.d + self.d * self.C) / seq
        fwd = mat + attn + head
        bwd = mat + 2 * attn + head
        return fwd + bwd + 4 * self.d * self.L
