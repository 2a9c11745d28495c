"""Configuration dataclasses shared across the framework.

Everything here is a frozen dataclass so configs are hashable and can be
closed over by jit'd functions without retracing surprises.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Adapter / PEFT configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AdapterCfg:
    """Configuration of the injected adapter (the paper's contribution).

    kind:
      'none'      - no adapter params in the tree.
      'hadamard'  - the paper: per-layer w (init 1) and b (init 0) vectors of
                    size d_model applied elementwise to the attention-block
                    output (Eq. 5/7).
      'lora'      - low-rank A@B deltas on wq/wv (baseline).
      'houlsby'   - bottleneck adapter after attn and after FFN (baseline).
      'ia3'       - IA3 scale vectors on k, v, and ffn activations (baseline).
    position:
      'attn_out'    - after the attention out-projection (default; fuses with
                      the residual+norm that follows on TPU).
      'attn_concat' - literal Eq. 7 placement: on Concat(heads), before W_O.
    """

    kind: str = "none"
    position: str = "attn_out"
    # LoRA baseline options
    lora_rank: int = 8
    lora_alpha: float = 16.0
    # Houlsby bottleneck width
    houlsby_dim: int = 64
    # Restrict the adapter to the top-k layers (paper Table 5); None = all.
    top_layers: Optional[int] = None

    @property
    def enabled(self) -> bool:
        return self.kind != "none"


# ---------------------------------------------------------------------------
# MoE configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoECfg:
    """capacity_factor None routes dropless: every token reaches each of
    its top_k experts through a grouped matmul over the held experts'
    sorted assignments, whatever else shares the batch. `held` is
    (first, count): the experts this chip holds of `n_experts` when each
    layer is divided over several chips (expert parallelism; dropless
    only). The router still scores all `n_experts` and takes `top_k`;
    the layer adds only its held experts' part of the result, and the
    shared experts in full. None holds every expert."""

    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    capacity_factor: Optional[float] = 1.25
    normalize_weights: bool = True
    router_dtype: str = "float32"
    aux_loss_weight: float = 0.01
    held: Optional[Tuple[int, int]] = None

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)


# ---------------------------------------------------------------------------
# Latent attention (MLA) and YaRN rope
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MLACfg:
    """Multi-head latent attention (DeepSeek-V2), with an uncompressed
    query: q is d -> heads x (nope_dim + rope_dim); the keys and values
    come from a `kv_rank` latent (normed) plus one shared `rope_dim` key
    part, which is all the cache holds: kv_rank + rope_dim per token per
    layer. Each head's value is v_dim wide (`ModelCfg.head_dim`)."""

    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def cache_dim(self) -> int:
        """Width of a cache leaf: latent_dim rounded up to the TPU's 128
        lanes, the rest zeros (see models/mla.py)."""
        return -(-self.latent_dim // 128) * 128


@dataclass(frozen=True)
class YarnCfg:
    """YaRN rope scaling as DeepSeek-V2 publishes it (`rope_scaling` of
    type 'yarn'): frequencies blend between interpolated (by `factor`)
    and original over a ramp set by beta_fast/beta_slow rotations within
    `original_max` positions; the attention scale gains mscale^2."""

    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


# ---------------------------------------------------------------------------
# Layer program: pattern groups of heterogeneous block slots
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Slot:
    """One block position inside a repeating pattern.

    kind: 'attn' | 'rec' (RG-LRU) | 'rwkv' (RWKV6 time-mix)
    window: local attention window (None = full attention)
    moe: FFN of this block is a mixture of experts
    cross_attn: decoder block with encoder cross-attention (enc-dec family)
    """

    kind: str = "attn"
    window: Optional[int] = None
    moe: bool = False
    cross_attn: bool = False


@dataclass(frozen=True)
class Group:
    """`repeats` copies of the slot pattern, scanned with stacked params."""

    slots: Tuple[Slot, ...]
    repeats: int

    @property
    def n_layers(self) -> int:
        return len(self.slots) * self.repeats


def dense_stack(n_layers: int, window: Optional[int] = None) -> Tuple[Group, ...]:
    return (Group(slots=(Slot(kind="attn", window=window),), repeats=n_layers),)


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelCfg:
    name: str
    family: str  # 'decoder' | 'encoder' | 'encdec' | 'vlm'
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    groups: Tuple[Group, ...]
    # enc-dec only: encoder stack (decoder stack lives in `groups`)
    enc_groups: Tuple[Group, ...] = ()

    moe: Optional[MoECfg] = None
    adapter: AdapterCfg = AdapterCfg()

    norm: str = "rmsnorm"  # 'rmsnorm' | 'layernorm'
    norm_eps: float = 1e-6
    ln_placement: str = "pre"  # 'pre' | 'post' (BERT-style)
    post_norms: bool = False  # gemma2: extra norm after attn/ffn sublayer out

    act: str = "silu"
    gated_mlp: bool = True
    attn_bias: bool = False
    mlp_bias: bool = False

    pos: str = "rope"  # 'rope' | 'learned' | 'none'
    rope_theta: float = 10000.0
    max_seq_len: int = 8192
    qk_norm: bool = False
    mla: Optional[MLACfg] = None  # latent attention (head_dim = v_dim)
    rope_scaling: Optional[YarnCfg] = None
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    query_scale: Optional[float] = None  # default 1/sqrt(head_dim)

    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma-style sqrt(d) embedding multiplier

    # encoder-classifier (BERT family) extras
    n_segment_types: int = 0
    pooler: bool = False
    n_classes: int = 2
    is_regression: bool = False

    # RG-LRU (recurrentgemma) extras
    lru_width: Optional[int] = None
    conv1d_width: int = 4

    # RWKV extras
    rwkv_head_dim: int = 64
    rwkv_chunk: int = 128  # remat-chunk length of the WKV recurrence

    # modality frontends (stubs per task spec)
    n_image_tokens: int = 0  # vlm: precomputed patch embeddings
    n_audio_frames: int = 1500  # whisper: precomputed frame embeddings

    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    # distribution profile: 'tp' | 'tp_fsdp' (adds data-axis weight sharding)
    shard_profile: str = "tp"
    # shard the token dim of inter-block activations over the model axis
    sequence_sharding: bool = True
    remat: bool = True
    # remat policy: 'none' = nothing saveable; 'dots' = save matmul outputs
    # (compute-vs-memory lever: skips the fwd recompute in backward)
    remat_policy: str = "none"
    # attention chunking (flash-style jnp path)
    q_chunk: int = 512
    kv_chunk: int = 1024
    # perf levers (§Perf; default OFF = paper-faithful baseline)
    replicate_kv: bool = False  # materialize K/V once per layer across the
    #   model axis instead of re-gathering per flash chunk iteration
    ce_chunk: int = 0  # sequence-chunked cross-entropy (0 = off)
    # flash-attention tile matmul dtype ('bfloat16' = MXU tiles with fp32
    # accumulation; softmax stats stay fp32 either way)
    attn_tile_dtype: str = "float32"

    def replace(self, **kw) -> "ModelCfg":
        return dataclasses.replace(self, **kw)

    # -- derived ------------------------------------------------------------

    @property
    def n_layers(self) -> int:
        return sum(g.n_layers for g in self.groups) + sum(
            g.n_layers for g in self.enc_groups
        )

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def attention_free(self) -> bool:
        kinds = {s.kind for g in self.groups for s in g.slots}
        return "attn" not in kinds

    @property
    def sub_quadratic(self) -> bool:
        """True if no block attends over an unbounded range (long-ctx okay)."""
        for g in tuple(self.groups) + tuple(self.enc_groups):
            for s in g.slots:
                if s.kind == "attn" and s.window is None:
                    return False
        return True


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (LM-family; seq_len x global_batch)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Optimizer / training configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimCfg:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    schedule: str = "cosine"  # 'constant' | 'linear' | 'cosine'
    warmup_steps: int = 0
    total_steps: int = 1000
    min_lr_ratio: float = 0.1
    # int8 error-feedback gradient compression (distributed-optimization knob)
    compress_grads: bool = False
    # AdamW moment storage (repro.optim.qstate): 'float32' (exact, the
    # historical state layout, bit-for-bit), 'bfloat16', or 'int8'
    # (block-wise QTensor behind the repro.quant primitive). Selected
    # per-moment; the memory-lean pretraining preset is m bf16 + v int8.
    m_dtype: str = "float32"
    v_dtype: str = "float32"
    # error-feedback residual for int8 moments (defeats the 8-bit grid's
    # deadzone; costs one extra int8 tree per int8 moment)
    qstate_ef: bool = True


@dataclass(frozen=True)
class TrainCfg:
    optim: OptimCfg = OptimCfg()
    batch_size: int = 16
    seq_len: int = 128
    steps: int = 100
    eval_every: int = 50
    microbatch: int = 0  # 0 = no gradient accumulation
    seed: int = 0
    log_every: int = 10


# TPU v5e hardware model used by the roofline analysis.
@dataclass(frozen=True)
class HardwareCfg:
    peak_flops_bf16: float = 197e12  # per chip
    hbm_bandwidth: float = 819e9  # bytes/s per chip
    ici_bandwidth: float = 50e9  # bytes/s per link
    hbm_bytes: float = 16e9  # v5e HBM capacity


V5E = HardwareCfg()
