"""Speculative multi-token decoding inside the continuous-batching tick.

The Hadamard serving story makes self-speculation unusually cheap: every
tenant is the SAME frozen backbone plus a per-task elementwise affine
(w, b), so the adapter-free backbone (identity rows w=1, b=0) is a free,
always-resident draft model - no second checkpoint, no extra HBM beyond a
second slot-cache pool. A `DraftLane` drafts k greedy tokens per tick in
one fused `lax.scan`, then the target scores all k+1 positions (the last
accepted token + k drafts) in ONE verify forward; per-slot host-side
acceptance keeps the longest draft prefix that matches the target's
greedy argmax and emits one correction token on top.

Guarantees:
  * Greedy speculative decoding is token-for-token identical to plain
    greedy decoding - acceptance-by-argmax-match makes every emitted
    token the target's own greedy choice by induction, regardless of
    draft quality (a bad draft only costs speed, never tokens).
  * Rollback is by overwrite, not by copy: a verify writes KV for
    positions p..p+k; after accepting `a` drafts the next tick's write
    range starts at p+a+1, which is <= p+k, so every rejected position is
    rewritten before any causal mask admits it. No KV is ever copied or
    zeroed on rejection.
  * Mixed tenants share the tick: sampled (top_k > 0) slots ride the same
    fixed-shape draft+verify jits - their token is drawn from the verify
    logits at position 0, which per-query causal masking makes
    bit-identical to the plain decode distribution - and advance one
    position per tick (their rejected draft range is the a=0 rollback
    case). The tick shape never depends on the accept pattern, so the
    zero-retrace invariant holds: `trace_counts` pins one compile for
    draft and one for verify across any number of adapter swaps.

Restrictions:
  * Full-attention targets only. A windowed ring cache of size `window`
    cannot host speculation: the k draft writes evict ring entries that
    earlier verify queries still need - a mask can hide stale data but
    cannot recover evicted data - so construction raises for any config
    with windowed or non-attention slots (`Scheduler.supports_bucketing`
    is exactly this predicate).
  * Self-speculation needs `adapter.kind == 'hadamard'` (the identity
    row IS the backbone). Any other adapter kind must bring a separate
    draft model (`draft=(cfg, params)`, same vocab).
  * The draft lane always decodes against its own contiguous slot caches
    even when the TARGET is paged - draft staleness can only lower the
    acceptance rate, never correctness, so the draft skips the paging
    machinery entirely.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import tree as tu
from repro.core.hadamard import ADAPTER_RE
from repro.dist.sharding import params_shardings, slot_cache_shardings
from repro.models import model as M
from repro.serving.paged import PagedScheduler
from repro.serving.scheduler import Request, Scheduler


class DraftLane:
    """The draft half of speculation: its own contiguous slot-cache pool
    plus two jits (admission prefill, fused k-step greedy draft scan).

    Self-speculation (draft=None) drafts with the engine's LIVE backbone
    under an identity adapter. The identity leaves are cached once, but
    the full draft tree is re-grafted from `engine.bank`/`engine.params`
    on EVERY call: hot-swap row inserts donate and rebind the bank tree,
    so a captured reference would go stale after the first swap. Grafting
    is a tree map (host-side, no copies) - backbone leaves are shared
    with the target by reference.

    A separate draft model (draft=(cfg, params)) must share the target's
    vocab; it is placed once (mesh-sharded when the engine has a mesh).
    """

    def __init__(self, engine, num_slots: int, max_len: int, k: int, *,
                 draft: Optional[Tuple] = None):
        if k < 1:
            raise ValueError("spec_k must be >= 1")
        self.engine = engine
        self.k = k
        self.max_len = max_len
        self._ident = {}
        if draft is None:
            if engine.cfg.adapter.kind != "hadamard":
                raise ValueError(
                    "self-speculation drafts with the adapter-free frozen "
                    "backbone (identity Hadamard rows w=1, b=0), which "
                    f"requires adapter.kind='hadamard' (got "
                    f"{engine.cfg.adapter.kind!r}); pass a separate draft "
                    "model via draft=(cfg, params)")
            self.cfg = engine.cfg
            self._sep = None

            def ident(path, leaf):
                if ADAPTER_RE.search(path):
                    # bank leaves are (L, T, d) (stacked task rows); a
                    # single-model draft leaf is (L, d)
                    shape = ((leaf.shape[0], leaf.shape[-1])
                             if leaf.ndim == 3 else leaf.shape)
                    self._ident[path] = (
                        jnp.ones(shape, leaf.dtype) if path.endswith("/w")
                        else jnp.zeros(shape, leaf.dtype))
                return leaf

            tu.map_with_path(ident, self._live())
        else:
            dcfg, dparams = draft
            if dcfg.vocab_size != engine.cfg.vocab_size:
                raise ValueError(
                    f"draft model vocab {dcfg.vocab_size} != target vocab "
                    f"{engine.cfg.vocab_size}: drafted token ids would not "
                    "be target tokens")
            self.cfg = dcfg
            self._sep = (dparams if engine.mesh is None else jax.device_put(
                dparams, params_shardings(dparams, dcfg, engine.mesh)))

        self.caches = M.init_decode_caches(self.cfg, num_slots, max_len)
        if engine.mesh is not None:
            self.caches = jax.device_put(
                self.caches,
                slot_cache_shardings(self.caches, self.cfg, engine.mesh))
        self.trace_counts = {"prefill": 0, "draft": 0}
        cfg = self.cfg

        def _pf(p, toks, cl, lp):
            self.trace_counts["prefill"] += 1
            return M.prefill_lm(p, cfg, toks, cache_len=cl, last_pos=lp)

        def _dk(p, caches, tok, pos):
            self.trace_counts["draft"] += 1

            def body(carry, _):
                caches, tok, pos = carry
                logits, caches = M.decode_lm(p, cfg, caches, tok[:, None],
                                             pos)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (caches, nxt, pos + 1), nxt

            # k+1 steps: the extra step writes the k-th draft's KV so an
            # all-accept tick leaves no gap in the draft cache (its output
            # token is discarded)
            (caches, _, _), outs = jax.lax.scan(
                body, (caches, tok, pos), None, length=self.k + 1)
            return jnp.moveaxis(outs, 0, 1)[:, :self.k], caches

        self._prefill_jit = jax.jit(_pf, static_argnums=(2,))
        self._admit_jit = jax.jit(
            lambda pool, row, slot: jax.tree.map(
                lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                    a, b.astype(a.dtype), slot, axis=1),
                pool, row),
            donate_argnums=(0,))
        self._draft_jit = jax.jit(_dk, donate_argnums=(1,))

    def _live(self):
        bank = getattr(self.engine, "bank", None)
        return bank if bank is not None else self.engine.params

    def _params(self):
        """The draft param tree for THIS call (see class docstring)."""
        if self._sep is not None:
            return self._sep
        return tu.map_with_path(
            lambda p, v: self._ident.get(p, v), self._live())

    def admit(self, slot_idx: int, prompt: np.ndarray, last_pos: int):
        """Prefill `prompt` ((1, S_pad) right-padded) through the draft
        model and scatter the fresh cache into the lane's slot row. Runs
        on EVERY admission - including target-side full prefix-cache hits,
        which skip the target prefill but still need draft KV."""
        with self.engine._mesh_ctx():
            _, fresh = self._prefill_jit(
                self._params(), jnp.asarray(prompt), self.max_len,
                jnp.int32(last_pos))
            self.caches = self._admit_jit(self.caches, fresh,
                                          jnp.int32(slot_idx))

    def draft(self, tok, pos):
        """Greedy-draft k tokens per row: feed `tok` ((B,) the last
        accepted target token) at `pos` ((B,)) and chain argmax on-device.
        Returns (B, k) drafted tokens; the lane's caches advance through
        position pos+k (stale suffixes are overwritten next tick)."""
        with self.engine._mesh_ctx():
            toks, self.caches = self._draft_jit(
                self._params(), self.caches, jnp.asarray(tok, jnp.int32),
                jnp.asarray(pos, jnp.int32))
        return toks


class _SpecMixin:
    """Shared verify-tick tail: acceptance, emission, accounting."""

    _sched_kind = "spec"

    def _init_spec(self, engine, num_slots: int, max_len: int, spec_k: int,
                   draft: Optional[Tuple]) -> None:
        """Shared tail of both spec scheduler constructors: the draft lane
        plus the speculation counters, registered once on the scheduler's
        obs registry (the old per-class `spec_stats` dicts were identical
        copy-pastes; `spec_stats` is now a read-only view of these)."""
        self.draft_lane = DraftLane(engine, num_slots, max_len, spec_k,
                                    draft=draft)
        # effective speculation depth (admission ladder steps it down
        # without retracing: the k+1 draft/verify shapes stay compiled,
        # only the host-side acceptance cap moves)
        self.spec_k_eff = spec_k
        self._g_spec_k = self.obs.gauge("serve_spec_k_effective",
                                        sched=self._sched_kind)
        self._g_spec_k.set(spec_k)
        self._c_drafted = self.obs.counter("serve_spec_drafted_total")
        self._c_accepted = self.obs.counter("serve_spec_accepted_total")
        self._c_spec_ticks = self.obs.counter("serve_spec_ticks_total")
        self.obs.add_derived("spec_acceptance_rate",
                             lambda: self.acceptance_rate)
        self._watch_traces("draft_lane", self.draft_lane.trace_counts)

    def set_spec_k(self, k: int) -> None:
        """Set the effective speculation depth, 0 <= k <= spec_k. Safe at
        any moment between ticks: reservations and headroom guards keep
        using the static `spec_k` worst case, the draft/verify jits keep
        their compiled shapes, and acceptance-by-argmax keeps greedy
        output token-identical at every depth. k=0 routes whole ticks
        through the plain decode path; the idle draft lane's cache gap
        only lowers acceptance after stepping back up, never
        correctness."""
        if not 0 <= k <= self.spec_k:
            raise ValueError(
                f"effective spec_k must be in [0, {self.spec_k}], got {k}")
        if k == self.spec_k_eff:
            return
        self.spec_k_eff = k
        self._g_spec_k.set(k)
        self.obs.event("spec_depth", sched=self._sched_kind, spec_k=k)

    @property
    def spec_stats(self) -> dict:
        """Read-only view of the speculation counters (kept for test/bench
        compatibility; the registry series are the source of truth)."""
        return {"drafted": self._c_drafted.value,
                "accepted": self._c_accepted.value,
                "spec_ticks": self._c_spec_ticks.value}

    def _check_spec_target(self, engine, spec_k: int):
        if spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        if not Scheduler.supports_bucketing(engine.cfg):
            raise ValueError(
                "speculative decoding requires full-attention slots: a "
                "windowed ring cache evicts entries the earlier verify "
                "queries still need when the k draft positions are "
                "written (masks can hide stale data, not recover evicted "
                "data); recurrent state folds the drafts in outright")

    def _submit_spec(self, req: Request) -> None:
        """Headroom guard: a verify may write up to spec_k positions past
        the final emitted token, and those writes must stay in range."""
        S = int(np.asarray(req.prompt).shape[-1])
        if S + req.max_new_tokens + self.spec_k > self.max_len:
            raise ValueError(
                f"prompt_len {S} + max_new_tokens {req.max_new_tokens} + "
                f"spec_k {self.spec_k} exceeds cache length {self.max_len} "
                "(speculative verify writes up to spec_k positions past "
                "the token budget)")

    def _admit_draft(self, slot_idx: int, req: Request) -> None:
        """Mirror a successful target admission into the draft lane (same
        padded shape so both lanes reuse one compiled prefill per
        bucket)."""
        if self.slots[slot_idx] is None:
            return  # finished at its first token: nothing left to draft
        prompt = np.asarray(req.prompt, np.int32).reshape(1, -1)
        S = prompt.shape[1]
        P = self._spec_padded_len(S)
        if P > S:
            prompt = np.pad(prompt, ((0, 0), (0, P - S)))
        self.draft_lane.admit(slot_idx, prompt, last_pos=S - 1)

    def _spec_emit(self, occupied: List[int], toks, logits) -> int:
        """Per-slot acceptance of the drafted `toks` (B, k+1) against the
        verify logits (B, k+1, V), after pulling both (`serve.sample_wait`).
        Greedy slots emit their accepted prefix plus the correction token;
        sampled slots draw ONE token from position 0's distribution.
        Acceptance is capped at the EFFECTIVE depth (admission ladder);
        drafted counts the static k - that is the draft work actually
        spent, which is what the acceptance-rate objective should see."""
        with self._span("sample_wait"):
            greedy = np.asarray(jnp.argmax(logits, axis=-1))  # (B, k+1)
            toks_h = np.asarray(toks)
        with self._span("emit"):
            k = self.spec_k_eff
            self._c_spec_ticks.inc()
            produced = 0
            for i in occupied:
                st = self.slots[i]
                if st.req.top_k and st.rng is not None:
                    # logits[:, 0] is bit-identical to plain decode (causal
                    # masks hide every draft write); rejected drafts are the
                    # a=0 rollback case
                    st.pos += 1
                    tok = self._sample_one(logits[i:i + 1, :1], st)
                    st.next_tok = tok
                    produced += 1
                    if not self._emit(i, st, tok):
                        self._tok[i] = tok
                        self._pos[i] = st.pos
                    continue
                a = 0
                while a < k and toks_h[i, a + 1] == greedy[i, a]:
                    a += 1
                self._c_drafted.inc(self.spec_k)
                self._c_accepted.inc(a)
                st.trace.mark("verify", accepted=a, drafted=k)
                done = False
                tok = 0
                for j in range(a + 1):  # a accepted drafts + the correction
                    st.pos += 1
                    tok = int(greedy[i, j])
                    st.next_tok = tok
                    produced += 1
                    if self._emit(i, st, tok):
                        done = True
                        break
                if not done:
                    self._tok[i] = tok
                    self._pos[i] = st.pos
            self._post_tick()
        return produced

    @property
    def acceptance_rate(self) -> float:
        d = self._c_drafted.value
        return self._c_accepted.value / d if d else 0.0


class SpecScheduler(_SpecMixin, Scheduler):
    """Continuous batching with speculative multi-token decoding over the
    contiguous slot-cache pool. Drop-in for `Scheduler` (same
    submit/step/run surface); greedy output is token-identical, each tick
    emits between 1 and spec_k+1 tokens per greedy slot.

    draft: None for self-speculation (identity-adapter backbone) or a
    (cfg, params) separate draft model sharing the target vocab.
    """

    def __init__(self, engine, *, num_slots: int, max_len: int,
                 spec_k: int = 4, draft: Optional[Tuple] = None,
                 stream=None, prefill_bucket: Optional[int] = None,
                 obs=None):
        self._check_spec_target(engine, spec_k)
        super().__init__(engine, num_slots=num_slots, max_len=max_len,
                         stream=stream, prefill_bucket=prefill_bucket,
                         obs=obs)
        self.spec_k = spec_k
        self._init_spec(engine, num_slots, max_len, spec_k, draft)

    def _spec_padded_len(self, S: int) -> int:
        if self.prefill_bucket is None:
            return S
        return min(self.max_len,
                   -(-S // self.prefill_bucket) * self.prefill_bucket)

    def submit(self, req: Request) -> int:
        self._submit_spec(req)
        return super().submit(req)

    def _admit_one(self, slot_idx, rid, req, submit_t):
        kind = super()._admit_one(slot_idx, rid, req, submit_t)
        self._admit_draft(slot_idx, req)
        return kind

    def _step_impl(self) -> int:
        if self.spec_k_eff == 0:
            # fully stepped down: plain one-token decode ticks (the first
            # compile of `decode` here is within the retrace allowance)
            return Scheduler._step_impl(self)
        self._do_admissions()
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return 0
        with self._span("plan"):
            tok = jnp.asarray(self._tok)
            pos = jnp.asarray(self._pos)
            task = self._task.copy()
        with self._span("decode"):
            drafts = self.draft_lane.draft(tok, pos)  # (B, k)
            toks = jnp.concatenate([tok[:, None], drafts], axis=1)  # (B, k+1)
            logits, self.caches = self.engine.verify_step(
                self.caches, toks, pos, task_ids=task)
        self._ticks += 1
        return self._spec_emit(occupied, toks, logits)


class SpecPagedScheduler(_SpecMixin, PagedScheduler):
    """Speculative decoding over the paged block pool: the verify tick
    writes k+1 positions per row through the block tables, so admission
    reserves spec_k extra worst-case positions and the allocate-on-write
    loop hands out every page the tick's write range can touch BEFORE the
    verify runs (the reservation invariant keeps this infallible). The
    draft lane stays contiguous (see module docstring); prefix-cache
    publication is untouched - published full pages sit strictly below
    the prompt tail, and any stale verify suffix in the tail block is
    rewritten before a reader's mask admits it.
    """

    _sched_kind = "spec_paged"

    def __init__(self, engine, *, num_slots: int, num_blocks: int, page: int,
                 max_len: int, spec_k: int = 4, draft: Optional[Tuple] = None,
                 kv_quant: Optional[str] = None, prefix_cache: bool = True,
                 stream=None, prefill_bucket: Optional[int] = None,
                 obs=None):
        self._check_spec_target(engine, spec_k)
        self.spec_k = spec_k  # _nb_worst needs it during super().__init__
        super().__init__(engine, num_slots=num_slots, num_blocks=num_blocks,
                         page=page, max_len=max_len, kv_quant=kv_quant,
                         prefix_cache=prefix_cache, stream=stream,
                         prefill_bucket=prefill_bucket, obs=obs)
        self._init_spec(engine, num_slots, max_len, spec_k, draft)

    def _spec_padded_len(self, S: int) -> int:
        return self._padded_len(S)

    def _nb_worst(self, S: int, max_new: int, P: int) -> int:
        """spec_k extra positions: the final tick's verify writes through
        position S + max_new + spec_k - 1."""
        return max(P // self.page,
                   -(-(S + max_new + self.spec_k) // self.page))

    def submit(self, req: Request) -> int:
        self._submit_spec(req)
        return super().submit(req)

    def _admit_one(self, slot_idx, rid, req, submit_t):
        kind = super()._admit_one(slot_idx, rid, req, submit_t)
        self._admit_draft(slot_idx, req)
        return kind

    def _step_impl(self) -> int:
        if self.spec_k_eff == 0:
            # fully stepped down: plain paged decode ticks
            return PagedScheduler._step_impl(self)
        self._do_admissions()
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return 0
        with self._span("plan"):
            # allocate-on-write, widened to the verify's whole write range
            # pos..pos+k: every page it can touch must be real BEFORE the
            # tick (the null block would silently swallow accepted KV)
            for i in occupied:
                st = self.slots[i]
                p0 = int(self._pos[i])
                for j in range(p0 // self.page,
                               min((p0 + self.spec_k) // self.page,
                                   st.nb_worst - 1) + 1):
                    if not self.tables[i, j]:
                        self.tables[i, j] = self.alloc.alloc()
                        st.nb_entries += 1
                        self._reserved -= 1
            tok = jnp.asarray(self._tok)
            pos = jnp.asarray(self._pos)
            task = self._task.copy()
        with self._span("decode"):
            drafts = self.draft_lane.draft(tok, pos)  # (B, k)
            toks = jnp.concatenate([tok[:, None], drafts], axis=1)  # (B, k+1)
            logits, self.pool = self.engine.paged_verify_step(
                self.pool, toks, pos, self.tables, task_ids=task)
        self._ticks += 1
        return self._spec_emit(occupied, toks, logits)
