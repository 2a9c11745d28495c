"""Continuous-batching request scheduler over a slot-based KV cache pool.

`ServeEngine.generate` is a lock-step static batch: every request must
arrive together, share one sequence-length budget, and the batch ends when
the longest request ends. Production traffic is nothing like that - this
module is the repo's answer, the Hadamard analogue of multi-LoRA serving:
one frozen (possibly mesh-sharded) backbone, a megabytes-sized bank of
per-task adapters, and a stream of heterogeneous requests.

Design (slot model):
  * The scheduler owns `num_slots` cache slots - rows of one pooled decode
    cache of length `max_len` (`engine.init_slot_caches`). Slot i's row is
    its private cache region; every request's positions start at 0 within
    its own row.
  * Admission is prefill-on-admit: a queued request is prefilled (B=1,
    cache_len=max_len) and its fresh cache row is scattered into the pool
    at the free slot's index - one jitted `dynamic_update_slice` on the
    slot axis, mid-decode, without touching other slots.
  * Every tick runs ONE fused decode step across all slots with per-slot
    position vectors (`decode_lm` with pos: (num_slots,)); each row
    attends over its own valid prefix via per-row kv_len masking in
    flash attention. Slots whose request carries a different task id are
    routed through the adapter-bank gather inside the same jitted step
    (`MultiTaskEngine.decode_step`), so heterogeneous tasks share every
    tick.
  * A slot retires the moment its request finishes (EOS or token budget)
    and is immediately reusable for the next queued request; inactive
    rows still flow through the fused step but their logits are ignored
    and their cache rows are fully overwritten on the next admission.

Greedy decoding is token-for-token identical to `ServeEngine.generate`
for the same prompts: per-row ops are batch-invariant, so neither the
B=1 prefill nor the fused per-slot tick changes any request's tokens.
"""
from __future__ import annotations

import sys
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry, StepWatchdog, phase_counters, span
from repro.obs.slo import SLOMonitor, SLOSpec
from repro.serving.admission import (AdmissionConfig, AdmissionController,
                                     AdmissionShedError)
from repro.serving.engine import check_temperature, sample_topk
from repro.serving.registry import BankFullError


@dataclass
class Request:
    """One generation request; arrives whenever, carries its own budget
    and sampling params, and (for MultiTaskEngine) its adapter: either a
    static bank row (`task_id`) or - for hot-swap engines - a registry
    `adapter` name, resolved to a live row at admission (loaded from disk
    on a bank miss, evicting the coldest unpinned row when full)."""

    prompt: np.ndarray  # (S,) int32 prompt tokens
    max_new_tokens: int
    top_k: int = 0  # 0 -> greedy
    temperature: float = 1.0
    seed: Optional[int] = None  # rng seed for top-k sampling
    task_id: int = 0  # adapter-bank row (MultiTaskEngine)
    adapter: Optional[str] = None  # adapter name (hot-swap MultiTaskEngine)
    eos_id: Optional[int] = None  # stop early on this token


@dataclass
class Completion:
    request_id: int
    tokens: np.ndarray  # generated tokens (includes the EOS token, if any)
    prompt_len: int
    task_id: int  # bank row the request ran under (resolved, for named)
    finish_reason: str  # 'eos' | 'length' | 'error' (adapter vanished)
    ttft_s: float  # submit -> first token (includes queueing)
    latency_s: float  # submit -> finished
    adapter: Optional[str] = None  # adapter name (named requests only)


@dataclass
class _Slot:
    request_id: int
    req: Request
    rng: Optional[jax.Array]
    tokens: List[int] = field(default_factory=list)
    next_tok: int = 0  # sampled, not yet fed through decode
    pos: int = 0  # absolute position of the next decode write
    row: int = 0  # resolved adapter-bank row (pinned while in flight)
    submit_t: float = 0.0
    first_tok_t: float = 0.0
    trace: object = None  # RequestTrace (set at admission; null when disabled)


class Scheduler:
    """Continuous-batching scheduler around a ServeEngine/MultiTaskEngine.

    stream: optional callback `(request_id, token)` invoked for every
    generated token the moment it is sampled.

    prefill_bucket: when set, prompts are right-padded to the next multiple
    of this bucket before prefill so arbitrary prompt lengths reuse a small
    set of compiled shapes (otherwise each distinct length compiles its own
    prefill). Token-exact, but only valid for full-attention configs - see
    the check in __init__.
    """

    _sched_kind = "contiguous"  # `sched=` label on every metric series
    # engine fns that must never recompile once serving started (prefill is
    # exempt: it legitimately compiles one shape per prompt-length bucket,
    # so its compiles are counted, not warned about)
    _RETRACE_KEYS = ("decode", "decode_paged", "verify", "verify_paged",
                     "draft")
    # host phases of a tick, each a `serve.<phase>` span: `tick` is the
    # whole step(); admit, plan, decode, sample_wait and emit are disjoint
    # parts of it; prefill_wait lies inside admit
    PHASES = ("tick", "admit", "prefill_wait", "plan", "decode",
              "sample_wait", "emit")

    def __init__(self, engine, *, num_slots: int, max_len: int,
                 stream: Optional[Callable[[int, int], None]] = None,
                 prefill_bucket: Optional[int] = None,
                 obs: Optional[MetricsRegistry] = None):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if prefill_bucket is not None and not self.supports_bucketing(
                engine.cfg):
            raise ValueError(
                "prefill_bucket requires full-attention slots (windowed "
                "ring caches and recurrent/rwkv state would fold the pad "
                "tokens in)")
        self.engine = engine
        self.num_slots = num_slots
        self.max_len = max_len
        self.stream = stream
        self.prefill_bucket = prefill_bucket
        self.caches = engine.init_slot_caches(num_slots, max_len)
        self.slots: List[Optional[_Slot]] = [None] * num_slots
        self.queue: deque = deque()
        self.completions: Dict[int, Completion] = {}
        self._next_id = 0
        self._ticks = 0
        # per-slot vectors fed to the fused decode step every tick
        self._tok = np.zeros((num_slots,), np.int32)
        self._pos = np.zeros((num_slots,), np.int32)
        self._task = np.zeros((num_slots,), np.int32)
        # one trace for every slot index: slot is a traced scalar
        self._admit = jax.jit(
            lambda pool, row, slot: jax.tree.map(
                lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                    a, b.astype(a.dtype), slot, axis=1),
                pool, row),
            donate_argnums=(0,))
        self._init_obs(obs)

    # -- observability ------------------------------------------------------

    def _init_obs(self, obs: Optional[MetricsRegistry]) -> None:
        """Create this scheduler's instruments on `obs` (or a fresh private
        registry). Called from __init__ by every scheduler flavour
        (PagedScheduler re-initializes rather than chaining to super)."""
        self.obs = obs if obs is not None else MetricsRegistry()
        kind = self._sched_kind
        self._m_submitted = self.obs.counter(
            "serve_requests_submitted_total", sched=kind)
        self._m_tokens = self.obs.counter("serve_tokens_total", sched=kind)
        self._m_ticks = self.obs.counter("serve_ticks_total", sched=kind)
        self._m_tick_s = self.obs.histogram("serve_tick_s", sched=kind)
        self._m_queue_s = self.obs.histogram("serve_queue_wait_s", sched=kind)
        self._m_ttft = self.obs.histogram("serve_ttft_s", sched=kind)
        self._m_tpot = self.obs.histogram("serve_tpot_s", sched=kind)
        self._m_latency = self.obs.histogram("serve_latency_s", sched=kind)
        self._m_retrace = self.obs.counter(
            "serve_retrace_events_total", sched=kind)
        self._m_prefill_compiles = self.obs.counter(
            "serve_prefill_compiles_total", sched=kind)
        # (seconds, calls) per phase: the counters each span increments
        self._m_phase = {p: phase_counters(self.obs, kind, p)
                         for p in self.PHASES}
        self._m_slow_ticks = self.obs.counter("serve_slow_ticks_total",
                                              sched=kind)
        # admission ticks run long, hence 4x rather than the train default
        self._tick_watch = StepWatchdog(factor=4.0)
        # admission-control instruments exist (at zero) even without an
        # attached controller, so report() keys are stable either way
        self._m_shed = self.obs.counter(
            "serve_admission_shed_total", sched=kind)
        self._m_deferred = self.obs.counter(
            "serve_admission_deferred_ticks_total", sched=kind)
        self._m_degrade_down = self.obs.counter(
            "serve_degrade_steps_total", sched=kind, direction="down")
        self._g_degrade_level = self.obs.gauge(
            "serve_degrade_level", sched=kind)
        self._g_queue_depth = self.obs.gauge("serve_queue_depth", sched=kind)
        self._slo_monitor: Optional[SLOMonitor] = None
        self._admission: Optional[AdmissionController] = None
        self._slo_check_every = 4
        self._pre_ticks = 0
        # retrace watch: baseline each jitted fn's compile count at init
        # (engines arrive with compile history from warmup / parity runs)
        self._trace_watch: List[tuple] = []
        self._trace_allow: Dict[tuple, int] = {}
        self._prefill_seen: Dict[str, int] = {}
        tc = getattr(self.engine, "trace_counts", None)
        if tc is not None:
            self._watch_traces("engine", tc)
        bank = getattr(self.engine, "adapter_bank", None)
        if bank is not None and hasattr(bank, "bind_obs"):
            bank.bind_obs(self.obs)

    def _watch_traces(self, src: str, trace_counts: dict) -> None:
        """Watch a trace-count dict for mid-serve recompiles. The allowance
        is current-count + 1: the first compile of each fn (possibly during
        this serve) is legitimate, anything beyond it is a retrace."""
        self._trace_watch.append((src, trace_counts))
        for k in self._RETRACE_KEYS:
            if k in trace_counts:
                self._trace_allow[(src, k)] = trace_counts.get(k, 0) + 1
        self._prefill_seen[src] = trace_counts.get("prefill", 0)

    def _check_retraces(self) -> None:
        for src, tc in self._trace_watch:
            n = tc.get("prefill", 0)
            if n > self._prefill_seen[src]:
                self._m_prefill_compiles.inc(n - self._prefill_seen[src])
                self._prefill_seen[src] = n
            for k in self._RETRACE_KEYS:
                allow = self._trace_allow.get((src, k))
                if allow is None:
                    continue
                n = tc.get(k, 0)
                if n > allow:
                    extra = n - allow
                    self._m_retrace.inc(extra)
                    self.obs.event("retrace", source=src, fn=k, count=extra,
                                   message="recompiled mid-serve")
                    print(f"[repro.obs] WARNING: {src}.{k} recompiled "
                          f"mid-serve (x{extra}) - shapes are leaking into "
                          "the steady-state serving path", file=sys.stderr)
                    self._trace_allow[(src, k)] = n

    def _post_tick(self) -> None:
        """Per-tick bookkeeping shared by every scheduler flavour's decode
        tick, at the end of its emit phase: tick count, the zero-retrace
        invariant check and the prefill compile count."""
        self._m_ticks.inc()
        self._check_retraces()

    def _span(self, phase: str, **attrs) -> span:
        return span(self._m_phase[phase], "serve." + phase, **attrs)

    def _slow_tick(self, seconds: float, phase_s: Dict[str, float],
                   admits: int) -> None:
        """A tick the watchdog flagged: count it and record where its time
        went (`phase_s`, `admits`: the phase counters before the tick)."""
        self._m_slow_ticks.inc()
        self.obs.event(
            "slow_tick", sched=self._sched_kind, tick=self._ticks,
            seconds=seconds, baseline_s=self._tick_watch.stragglers[-1][2],
            admissions=self._m_phase["admit"][1].value - admits,
            phases={p: c.value - phase_s[p]
                    for p, (c, _) in self._m_phase.items() if p != "tick"})

    def _pre_tick(self) -> None:
        """Runs exactly once per `step()` call, BEFORE admissions - even on
        idle ticks, which is what lets an attached admission controller
        observe recovery and step back up while traffic is paused."""
        self._g_queue_depth.set(len(self.queue))
        self._pre_ticks += 1
        if self._admission is not None:
            self._admission.on_step(self)
        elif (self._slo_monitor is not None
                and self._pre_ticks % self._slo_check_every == 0):
            self._slo_monitor.evaluate()

    def attach_slo(self, spec: SLOSpec, *,
                   admission: Optional[AdmissionConfig] = None,
                   check_every: int = 4,
                   clock: Optional[Callable[[], float]] = None) -> SLOMonitor:
        """Attach SLO evaluation (and optionally admission control) to this
        scheduler's tick. With only a `spec`, objectives are evaluated
        every `check_every` ticks and breaches land as registry events;
        with an `AdmissionConfig` the degradation ladder in
        `repro.serving.admission` acts on them (its own check_every
        supersedes this one). `clock` injects a time source for
        deterministic window tests. Normally wired by `make_scheduler`
        from `ServingConfig(slo=, admission=)`."""
        kwargs = {"base_labels": {"sched": self._sched_kind}}
        if clock is not None:
            kwargs["clock"] = clock
        self._slo_monitor = SLOMonitor(self.obs, spec, **kwargs)
        self._slo_check_every = check_every
        if admission is not None:
            self._admission = AdmissionController(
                self, self._slo_monitor, admission)
        return self._slo_monitor

    @staticmethod
    def _tenant(st: _Slot) -> str:
        return st.req.adapter if st.req.adapter is not None else \
            f"task{st.row}"

    @staticmethod
    def supports_bucketing(cfg) -> bool:
        """Whether prompt-length bucketing is token-exact for this config.
        Bucketing right-pads prompts so prefill compiles one shape per
        bucket instead of one per distinct prompt length; that is correct
        only for full (non-windowed) attention caches, where the pad
        suffix is causally invisible at prefill and decode overwrites
        position p's cache entry before kv_len ever unmasks it."""
        return all(s.kind == "attn" and s.window is None
                   for g in cfg.groups for s in g.slots)

    # -- request lifecycle --------------------------------------------------

    def submit(self, req: Request) -> int:
        """Queue a request; returns its id. Admission happens on the next
        tick that has a free slot. Named-adapter requests are validated
        here (engine supports names + the name resolves in bank/registry)
        so the queue never holds a request that can never be admitted.

        Raises `AdmissionShedError` while an attached admission controller
        is shedding: the front door closes before any validation so the
        overloaded path stays cheap, and the typed error tells callers
        this is backpressure (retry later / reroute), not caller error."""
        if self._admission is not None and self._admission.shedding:
            objectives = self._admission.breaching_objectives
            self._m_shed.inc()
            self.obs.event("shed", sched=self._sched_kind,
                           level=self._admission.level,
                           objectives=list(objectives))
            raise AdmissionShedError(
                f"admissions shed at degrade level {self._admission.level}"
                f" (breaching: {', '.join(objectives) or 'recovering'})",
                level=self._admission.level, objectives=objectives)
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        check_temperature(req.temperature)
        S = int(np.asarray(req.prompt).shape[-1])
        if S + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt_len {S} + max_new_tokens {req.max_new_tokens} "
                f"exceeds slot cache length {self.max_len}")
        if req.adapter is not None:
            if getattr(self.engine, "adapter_bank", None) is None:
                raise ValueError(
                    "request names an adapter but the engine has no "
                    "AdapterBank (hot-swap MultiTaskEngine required)")
            if not self.engine.has_adapter(req.adapter):
                raise KeyError(
                    f"adapter {req.adapter!r} is neither bank-resident nor "
                    "published in the registry")
        rid = self._next_id
        self._next_id += 1
        self._m_submitted.inc()
        self.obs.tracer.start(rid).mark("submit", prompt_len=S)
        self.queue.append((rid, req, time.perf_counter()))
        return rid

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def pending(self) -> int:
        return len(self.queue)

    def _sample_one(self, logits_row, st: _Slot) -> int:
        """One request's sampling decision (logits_row: (1, 1, V))."""
        if st.req.top_k and st.rng is not None:
            st.rng, sub = jax.random.split(st.rng)
            return int(sample_topk(logits_row, sub, k=st.req.top_k,
                                   temperature=st.req.temperature)[0])
        return int(jnp.argmax(logits_row[:, -1], axis=-1)[0])

    def _emit(self, slot_idx: int, st: _Slot, tok: int) -> bool:
        """Record one generated token; returns True if the request is done."""
        if not st.tokens:
            st.first_tok_t = time.perf_counter()
            st.trace.mark("first_token")
        self._m_tokens.inc()
        st.tokens.append(tok)
        if self.stream is not None:
            self.stream(st.request_id, tok)
        if st.req.eos_id is not None and tok == st.req.eos_id:
            self._retire(slot_idx, st, "eos")
            return True
        if len(st.tokens) >= st.req.max_new_tokens:
            self._retire(slot_idx, st, "length")
            return True
        return False

    def _retire(self, slot_idx: int, st: _Slot, reason: str):
        now = time.perf_counter()
        ttft = st.first_tok_t - st.submit_t
        latency = now - st.submit_t
        n_tok = len(st.tokens)
        self.completions[st.request_id] = Completion(
            request_id=st.request_id,
            tokens=np.asarray(st.tokens, np.int32),
            prompt_len=int(np.asarray(st.req.prompt).shape[-1]),
            task_id=st.row,
            finish_reason=reason,
            ttft_s=ttft,
            latency_s=latency,
            adapter=st.req.adapter,
        )
        kind, tenant = self._sched_kind, self._tenant(st)
        self.obs.counter("serve_requests_completed_total", sched=kind,
                         reason=reason).inc()
        self._m_ttft.observe(ttft)
        self.obs.histogram("serve_ttft_s", sched=kind,
                           tenant=tenant).observe(ttft)
        self._m_latency.observe(latency)
        if n_tok > 1:
            tpot = (latency - ttft) / (n_tok - 1)
            self._m_tpot.observe(tpot)
            self.obs.histogram("serve_tpot_s", sched=kind,
                               tenant=tenant).observe(tpot)
        st.trace.mark("retire", reason=reason, tokens=n_tok)
        self.obs.tracer.finish(st.request_id)
        if st.req.adapter is not None:
            self.engine.release_adapter(st.req.adapter)  # unpin its row
        self.slots[slot_idx] = None  # immediately reusable

    def _admit_one(self, slot_idx: int, rid: int, req: Request,
                   submit_t: float) -> str:
        """Admit one request; returns its prefill kind. Raises
        BankFullError (before any state is touched) when the request names
        an adapter and every bank row is pinned - the caller defers the
        whole queue to a later tick."""
        row = req.task_id
        if req.adapter is not None:
            row = self.engine.acquire_adapter(req.adapter)  # pins the row
        tr = self.obs.tracer.get(rid)
        queue_s = time.perf_counter() - submit_t
        self._m_queue_s.observe(queue_s)
        tr.mark("admit", slot=slot_idx, row=row, adapter=req.adapter,
                queue_s=queue_s)
        prompt = np.asarray(req.prompt, np.int32).reshape(1, -1)
        S = prompt.shape[1]
        last_pos = None
        if self.prefill_bucket is not None:
            padded = min(self.max_len,
                         -(-S // self.prefill_bucket) * self.prefill_bucket)
            if padded > S:
                prompt = np.pad(prompt, ((0, 0), (0, padded - S)))
            last_pos = S - 1
        logits, fresh = self.engine.prefill(
            prompt, self.max_len, task_ids=np.asarray([row]),
            last_pos=last_pos)
        tr.mark("prefill", kind="cold", prompt_len=S)
        self.caches = self._admit(self.caches, fresh, jnp.int32(slot_idx))
        rng = (jax.random.PRNGKey(req.seed if req.seed is not None else rid)
               if req.top_k else None)
        st = _Slot(request_id=rid, req=req, rng=rng, pos=S, row=row,
                   submit_t=submit_t, trace=tr)
        self.slots[slot_idx] = st
        with self._span("prefill_wait"):
            st.next_tok = self._sample_one(logits, st)
        self._task[slot_idx] = row
        if not self._emit(slot_idx, st, st.next_tok):
            self._tok[slot_idx] = st.next_tok
            self._pos[slot_idx] = st.pos
        return "cold"

    # -- the tick -----------------------------------------------------------

    # admission failures that defer the queue to a later tick instead of
    # failing the request (paged schedulers add BlockPoolFullError)
    _defer_errors = (BankFullError,)

    def _do_admissions(self) -> None:
        """Admit queued requests into free slots. A request finishing at
        its first token frees the slot again, so keep admitting until
        slots or queue run out."""
        if (self._admission is not None and self._admission.deferring
                and self.queue and self.active):
            # degraded: queued requests wait while in-flight work drains.
            # The `self.active` guard is the liveness escape - with no
            # requests in flight nothing can retire to trigger recovery,
            # so an empty engine always admits (run() can never hang on a
            # deferred queue).
            self._m_deferred.inc()
            return
        free = [i for i, s in enumerate(self.slots) if s is None]
        while free and self.queue:
            idx = free.pop()
            rid, req, submit_t = self.queue.popleft()
            try:
                with self._span("admit", request_id=rid, slot=idx) as adm:
                    adm.set_metadata(
                        kind=self._admit_one(idx, rid, req, submit_t))
            except KeyError:
                # the adapter was validated at submit but unpublished (and
                # its row evicted) before admission - runtime removal is a
                # supported operation, so fail THIS request, not the loop
                now = time.perf_counter()
                self.completions[rid] = Completion(
                    request_id=rid, tokens=np.zeros((0,), np.int32),
                    prompt_len=int(np.asarray(req.prompt).shape[-1]),
                    task_id=-1, finish_reason="error", ttft_s=0.0,
                    latency_s=now - submit_t, adapter=req.adapter)
                self.obs.counter("serve_requests_completed_total",
                                 sched=self._sched_kind, reason="error").inc()
                tr = self.obs.tracer.get(rid)
                tr.mark("retire", reason="error", tokens=0)
                self.obs.tracer.finish(rid)
                free.append(idx)
            except self._defer_errors:
                # a shared resource (bank rows / pool blocks) is exhausted
                # by in-flight requests: put the request back (FIFO order
                # preserved) and retry once a retirement frees capacity.
                # Deliberately not skipping ahead to later queued requests
                # - reordering would starve the blocked tenant under
                # sustained traffic.
                self.obs.tracer.get(rid).mark("defer")
                self.queue.appendleft((rid, req, submit_t))
                free.append(idx)
                break
            if self.slots[idx] is None:
                free.append(idx)

    def step(self) -> int:
        """One scheduler tick: pre-tick hooks (queue gauge, SLO/admission
        evaluation), admissions into free slots, then one fused decode
        step across all occupied slots. Returns the number of tokens
        generated this tick. The body lives in `_step_impl` so flavours
        (and the spec schedulers' degraded plain-decode fallback) can
        delegate without re-running the pre-tick hooks.

        The whole tick is the `serve.tick` span and feeds `serve_tick_s`;
        ticks that ran a decode step also feed the slow-tick watchdog (an
        idle tick's microseconds would drag its baseline down)."""
        phase_s = {p: c.value for p, (c, _) in self._m_phase.items()}
        admits = self._m_phase["admit"][1].value
        with self._span("tick") as tick:
            self._pre_tick()
            produced = self._step_impl()
        self._m_tick_s.observe(tick.seconds)
        if produced and self._tick_watch.observe(self._ticks, tick.seconds):
            self._slow_tick(tick.seconds, phase_s, admits)
        return produced

    def _step_impl(self) -> int:
        self._do_admissions()
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return 0
        with self._span("plan"):
            tok = jnp.asarray(self._tok[:, None])
            pos = jnp.asarray(self._pos)
            task = self._task.copy()
        with self._span("decode"):
            logits, self.caches = self.engine.decode_step(
                self.caches, tok, pos, task_ids=task)
        self._ticks += 1
        return self._sample_and_emit(occupied, logits)

    def _sample_and_emit(self, occupied: List[int], logits) -> int:
        """The tail of a one-token decode tick: the argmax pull
        (`serve.sample_wait`), then each slot's token, stream callback and
        retirement, and the tick's bookkeeping (`serve.emit`)."""
        with self._span("sample_wait"):
            # one fused argmax covers every greedy slot; sampled slots draw
            # from their own rng stream individually
            any_greedy = any(not (self.slots[i].req.top_k
                                  and self.slots[i].rng is not None)
                             for i in occupied)
            greedy = (np.asarray(jnp.argmax(logits[:, -1], axis=-1))
                      if any_greedy else None)
        with self._span("emit"):
            produced = 0
            for i in occupied:
                st = self.slots[i]
                st.pos += 1
                if st.req.top_k and st.rng is not None:
                    tok = self._sample_one(logits[i:i + 1], st)
                else:
                    tok = int(greedy[i])
                st.next_tok = tok
                produced += 1
                if not self._emit(i, st, tok):
                    self._tok[i] = tok
                    self._pos[i] = st.pos
            self._post_tick()
        return produced

    # -- batch driver -------------------------------------------------------

    def run(self, requests: List[Request]):
        """Submit `requests`, tick until drained, and return
        (completions ordered by request id, throughput/latency report).
        Reusable: each call reports only its own ticks and pops its own
        completions (callers driving submit()/step() directly should pop
        from `self.completions` likewise to keep it bounded)."""
        t0 = time.perf_counter()
        ticks0 = self._ticks
        ids = [self.submit(r) for r in requests]
        while self.queue or self.active:
            self.step()
        elapsed = time.perf_counter() - t0
        done = [self.completions.pop(i) for i in ids]
        return done, self.report(done, elapsed, ticks=self._ticks - ticks0)

    def report(self, done=(), elapsed_s: float = 0.0,
               ticks: Optional[int] = None) -> dict:
        """Throughput/latency report. Counts and means cover `done` (this
        call's completions); the p50/p95/p99 TTFT and per-token-latency
        quantiles come from this scheduler's aggregate histograms, so they
        cover every request retired since construction."""
        done = list(done)
        n_tok = sum(len(c.tokens) for c in done)
        return {
            "requests": len(done),
            "tokens": n_tok,
            "elapsed_s": elapsed_s,
            "ticks": self._ticks if ticks is None else ticks,
            "requests_per_s": len(done) / elapsed_s if elapsed_s else 0.0,
            "tokens_per_s": n_tok / elapsed_s if elapsed_s else 0.0,
            "mean_ttft_s": (sum(c.ttft_s for c in done) / len(done)
                            if done else 0.0),
            "mean_latency_s": (sum(c.latency_s for c in done) / len(done)
                               if done else 0.0),
            "ttft_p50_s": self._m_ttft.percentile(0.50),
            "ttft_p95_s": self._m_ttft.percentile(0.95),
            "ttft_p99_s": self._m_ttft.percentile(0.99),
            "tpot_p50_s": self._m_tpot.percentile(0.50),
            "tpot_p95_s": self._m_tpot.percentile(0.95),
            "tpot_p99_s": self._m_tpot.percentile(0.99),
            # admission-control activity since construction (all zero
            # without an attached controller)
            "shed": self._m_shed.value,
            "deferred_ticks": self._m_deferred.value,
            "degrade_steps": self._m_degrade_down.value,
            "degrade_level": (self._admission.level
                              if self._admission is not None else 0),
        }


def format_report(report: dict) -> str:
    """Render a scheduler report dict as aligned human-readable lines
    (launch/serve prints this instead of recomputing its own report)."""
    lines = []
    for k, v in report.items():
        lines.append(f"  {k:<16} {v:.4f}" if isinstance(v, float)
                     else f"  {k:<16} {v}")
    return "\n".join(lines)
