"""Operations and bytes that the algorithm needs, from shapes alone.

Counts credit the work a request needs, not what a program happens to
touch: a decode step reads the weights once and each active row's live
KV (positions below its own), and writes one new KV entry per row; it
is not charged for cache padding up to `max_len`, for inactive rows, or
for prompt-bucket padding. Multiply-adds count as two operations.
Norms, RoPE, softmax and the adapter's elementwise affine are left out
(under 0.1% of the matmul work at these widths).
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


class Decoder:
    """Counts for a GQA decoder with a gated MLP and an lm head of the
    vocabulary's width (tied or not)."""

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


class Encoder:
    """Counts for a post-LN encoder classifier trained with a frozen
    backbone and trainable elementwise leaves (adapter, ffn norm)."""

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
