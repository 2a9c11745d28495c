"""One declarative config for the whole serving surface.

Every serving feature the repo has grown - continuous batching, prompt
bucketing, paged KV with prefix sharing, KV/backbone quantization,
speculative multi-token decoding - used to be reachable only by picking
the right scheduler class and threading the right constructor knobs.
`ServingConfig` + `make_scheduler` collapse that into one frozen config
validated up front (incoherent combinations fail at construction, not
three layers deep at runtime) and one factory that selects the scheduler:

    cfgS = ServingConfig(num_slots=8, max_len=512, paged=True,
                         page_size=16, kv_quant="int8", spec_k=4)
    sched = make_scheduler(engine, cfgS)
    done, report = sched.run(requests)

The factory is the supported construction path; the scheduler classes
remain importable for typing and subclassing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from repro.obs.slo import SLOSpec
from repro.serving.admission import AdmissionConfig

_QUANT_MODES = (None, "int8", "fp8")


@dataclass(frozen=True)
class ServingConfig:
    """Declarative serving configuration (validated at construction).

    Capacity:
      num_slots       concurrent sequences per tick
      max_len         per-sequence cache length (prompt + generation)
    Paged KV (serving/paged.py):
      paged           block-pool KV instead of per-slot rows
      page_size       tokens per block
      num_blocks      pool size; None sizes it to 1.5x the worst case
                      all slots can reserve (prefix-cache headroom)
      prefix_cache    cross-request COW prefix sharing
      kv_quant        'int8'/'fp8' KV blocks (paged only)
    Speculation (serving/spec.py):
      spec_k          draft tokens per tick; 0 disables speculation
      spec_draft      'self' (identity-adapter backbone) or 'model'
                      (pass draft_model=(cfg, params) to make_scheduler)
    Engine coherence:
      backbone_quant  expected engine weight quantization; make_scheduler
                      rejects an engine built with a different mode
    Prefill / sampling defaults / streaming:
      prefill_bucket  round prompt lengths up to multiples of this
      top_k           default sampling top-k for launchers building
                      Requests from raw prompts (0 = greedy)
      temperature     default sampling temperature for the same
      stream          optional (request_id, token) callback per token
    SLOs / admission control (obs/slo.py + serving/admission.py):
      slo             SLOSpec evaluated over the scheduler's metrics
                      (breaches land as registry events)
      admission       AdmissionConfig: act on breaches with the
                      degradation ladder (requires slo)
    """

    num_slots: int = 8
    max_len: int = 512
    paged: bool = False
    page_size: int = 16
    num_blocks: Optional[int] = None
    prefix_cache: bool = True
    kv_quant: Optional[str] = None
    spec_k: int = 0
    spec_draft: str = "self"
    backbone_quant: Optional[str] = None
    prefill_bucket: Optional[int] = None
    top_k: int = 0
    temperature: float = 1.0
    stream: Optional[Callable[[int, int], None]] = None
    slo: Optional[SLOSpec] = None
    admission: Optional[AdmissionConfig] = None

    def __post_init__(self):
        if self.admission is not None and self.slo is None:
            raise ValueError(
                "admission control needs objectives to act on: set slo= "
                "alongside admission=")
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.max_len < 1:
            raise ValueError("max_len must be >= 1")
        if self.kv_quant not in _QUANT_MODES:
            raise ValueError(f"kv_quant must be one of {_QUANT_MODES}")
        if self.backbone_quant not in _QUANT_MODES:
            raise ValueError(f"backbone_quant must be one of {_QUANT_MODES}")
        if self.kv_quant is not None and not self.paged:
            raise ValueError(
                "kv_quant requires paged=True: only the block pool stores "
                "quantized KV (the contiguous slot cache is fp32)")
        if self.paged:
            if self.page_size < 1:
                raise ValueError("page_size must be >= 1")
            if self.max_len % self.page_size:
                raise ValueError(
                    f"max_len {self.max_len} must be a multiple of "
                    f"page_size {self.page_size}")
            if self.num_blocks is not None and self.num_blocks < 2:
                raise ValueError(
                    "num_blocks must be >= 2 (block 0 is the null block)")
            if (self.prefill_bucket is not None
                    and self.prefill_bucket % self.page_size):
                raise ValueError(
                    "prefill_bucket must be a multiple of page_size "
                    "(pages are the unit of insert)")
        elif self.num_blocks is not None:
            raise ValueError("num_blocks requires paged=True")
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 disables speculation)")
        if self.spec_draft not in ("self", "model"):
            raise ValueError("spec_draft must be 'self' or 'model'")
        if self.spec_draft == "model" and not self.spec_k:
            raise ValueError(
                "spec_draft='model' is meaningless with spec_k=0")
        if self.prefill_bucket is not None and self.prefill_bucket < 1:
            raise ValueError("prefill_bucket must be >= 1")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")


def _auto_blocks(config: ServingConfig) -> int:
    """Default pool size: 1.5x the worst case every slot can reserve at
    once (headroom keeps the prefix cache useful under full load), plus
    the reserved null block."""
    per_slot = config.max_len // config.page_size
    return 1 + config.num_slots * per_slot * 3 // 2


def make_scheduler(engine, config: ServingConfig, *, draft_model=None,
                   obs=None):
    """Build the scheduler `config` describes around `engine`.

    draft_model: (cfg, params) for spec_draft='model'; forbidden
    otherwise (a silently ignored draft model would mask a config
    mistake).

    obs: a `repro.obs.MetricsRegistry` the scheduler should report into
    (launchers pass the one their exporters are attached to); None gives
    the scheduler a private registry, reachable as `sched.obs`.
    """
    if config.backbone_quant is not None \
            and getattr(engine, "quant", None) != config.backbone_quant:
        raise ValueError(
            f"config expects a backbone_quant={config.backbone_quant!r} "
            f"engine but the engine was built with "
            f"quant={getattr(engine, 'quant', None)!r}")
    draft = None
    if config.spec_k and engine.cfg.mla is not None:
        raise ValueError("speculative decoding (spec_k) is not supported "
                         "for latent attention (cfg.mla)")
    if config.spec_k:
        if config.spec_draft == "model":
            if draft_model is None:
                raise ValueError(
                    "spec_draft='model' requires draft_model=(cfg, params)")
            draft = draft_model
        elif draft_model is not None:
            raise ValueError(
                "draft_model given but spec_draft='self'; set "
                "spec_draft='model' to use it")
    elif draft_model is not None:
        raise ValueError("draft_model given but spec_k=0")

    if config.paged:
        from repro.serving.paged import PagedScheduler
        from repro.serving.spec import SpecPagedScheduler

        num_blocks = (config.num_blocks if config.num_blocks is not None
                      else _auto_blocks(config))
        if config.spec_k:
            sched = SpecPagedScheduler(
                engine, num_slots=config.num_slots, num_blocks=num_blocks,
                page=config.page_size, max_len=config.max_len,
                spec_k=config.spec_k, draft=draft,
                kv_quant=config.kv_quant, prefix_cache=config.prefix_cache,
                stream=config.stream, prefill_bucket=config.prefill_bucket,
                obs=obs)
        else:
            sched = PagedScheduler(
                engine, num_slots=config.num_slots, num_blocks=num_blocks,
                page=config.page_size, max_len=config.max_len,
                kv_quant=config.kv_quant, prefix_cache=config.prefix_cache,
                stream=config.stream, prefill_bucket=config.prefill_bucket,
                obs=obs)
    else:
        from repro.serving.scheduler import Scheduler
        from repro.serving.spec import SpecScheduler

        if config.spec_k:
            sched = SpecScheduler(
                engine, num_slots=config.num_slots, max_len=config.max_len,
                spec_k=config.spec_k, draft=draft, stream=config.stream,
                prefill_bucket=config.prefill_bucket, obs=obs)
        else:
            sched = Scheduler(
                engine, num_slots=config.num_slots, max_len=config.max_len,
                stream=config.stream,
                prefill_bucket=config.prefill_bucket, obs=obs)
    if config.slo is not None:
        sched.attach_slo(config.slo, admission=config.admission)
    return sched
