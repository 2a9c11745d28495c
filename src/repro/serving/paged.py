"""Paged KV cache: block-table serving with copy-on-write prefix sharing.

The slot scheduler (serving/scheduler.py) reserves `max_len` cache rows
per slot up front, so a 32-token chat and a 4k-token document both pin the
same worst-case region and slots-per-GB is set by the longest request you
might ever see. This module replaces that with the vLLM recipe adapted to
the repo's stacked-group layer program:

  * One device-resident block pool per attention slot - leaves of shape
    (repeats, num_blocks, page, KH, Dh), block 0 reserved as the null
    block (unallocated table entries point at it; reads of it are always
    masked, writes to it are harmless). Under `kv_quant` the leaves are
    int8/fp8 QTensors with per-token-per-head scales and the decode path
    dequantizes in-kernel. A latent-attention slot has one leaf instead,
    (repeats, num_blocks, page, mla.cache_dim), never quantized;
    the block accounting is the same (blocks per token).
  * One block table PER SEQUENCE, shared by every layer: the table maps
    logical block j -> physical block; the layer scan carries the stacked
    pool and each layer writes and gathers its own rows in place, while
    the table rides along unchanged. Tables live on the host as a
    stable-(num_slots, nb_max)-shaped int32 array, so the fused decode
    tick compiles exactly once.
  * A refcounted `BlockAllocator` plus a `PrefixCache` keyed by chained
    page hashes of the prompt (per adapter row - the Hadamard adapter
    rewrites K/V, so KV is only shareable between requests on the same
    task). Identical prefixes are prefilled once and shared read-only;
    a writer forks the partially-filled tail block copy-on-write. A full
    prompt hit skips the forward pass entirely and replays the stored
    last-token logits.
  * Admission reserves the worst case: a slot's remaining allocate-on-
    write budget stays subtracted from the free count, so a mid-decode
    page allocation can never fail and nothing is ever preempted. When
    free-minus-reserved can't cover an admission, the prefix cache is
    evicted LRU-first; if that still isn't enough, `BlockPoolFullError`
    defers the queue FIFO-fashion to a later tick (same contract as
    BankFullError).

Exactness: the gathered view a decode step attends over is always
nb_max * page == max_len entries - the same length, chunk decomposition
and masking as the contiguous slot cache - so paged fp32 greedy decoding
is token-for-token identical to the contiguous scheduler. Windowed slots
run the same ring layout inside the first ring//page table entries
(cold path only: ring caches fold positions, so prefix reuse is
restricted to full-attention configs).
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry
from repro.serving.registry import BankFullError
from repro.serving.scheduler import Completion, Request, Scheduler, _Slot


class BlockPoolFullError(RuntimeError):
    """Admission would overcommit the block pool (free - reserved < need)."""


class BlockAllocator:
    """Refcounted free-list over physical blocks 1..num_blocks-1.

    Block 0 is the reserved null block: never handed out, the parking
    target for unallocated table entries. A block's refcount counts its
    live readers - the owning slot's table entry plus every prefix-cache
    entry naming it; the block returns to the free list only when the
    last reader drops it.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        self.num_blocks = num_blocks
        # pop() hands out ascending ids - deterministic tables for tests
        self._free = list(range(num_blocks - 1, 0, -1))
        self._refs = [0] * num_blocks

    @property
    def num_free(self) -> int:
        return len(self._free)

    def refcount(self, bid: int) -> int:
        return self._refs[bid]

    def alloc(self) -> int:
        if not self._free:
            raise BlockPoolFullError("block pool exhausted")
        bid = self._free.pop()
        self._refs[bid] = 1
        return bid

    def incref(self, bid: int) -> None:
        if bid <= 0 or self._refs[bid] <= 0:
            raise ValueError(f"incref of unallocated block {bid}")
        self._refs[bid] += 1

    def decref(self, bid: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        if bid <= 0 or self._refs[bid] <= 0:
            raise ValueError(f"double free of block {bid}")
        self._refs[bid] -= 1
        if self._refs[bid] == 0:
            self._free.append(bid)
            return True
        return False


class PrefixCache:
    """LRU cache of prompt-prefix blocks, keyed by chained page hashes.

    Two tiers, both per adapter key (same-adapter sharing only):
      * `blocks`: (akey, chain_hash_j) -> physical block id for one FULL
        page of a retired prompt. Holds one allocator reference per entry.
      * `full`: (akey, S, chain_hash_all) -> (block ids covering the whole
        prompt incl. a partial tail, stored last-token logits). A hit
        skips prefill entirely. Holds one reference per listed block.

    Eviction (`evict_one`) pops the LRU `full` entry first - full entries
    pin the most blocks - then LRU `blocks` entries.
    """

    def __init__(self, obs: Optional[MetricsRegistry] = None):
        self.blocks: "OrderedDict[tuple, int]" = OrderedDict()
        self.full: "OrderedDict[tuple, Tuple[Tuple[int, ...], np.ndarray]]" \
            = OrderedDict()
        # the cache's own match counters ARE the hit metrics - the
        # scheduler reads them back instead of double-counting
        obs = obs if obs is not None else MetricsRegistry()
        self._c_full = obs.counter("serve_prefix_hits_total", tier="full")
        self._c_partial = obs.counter("serve_prefix_hits_total",
                                      tier="partial")

    @property
    def hits_full(self) -> int:
        return self._c_full.value

    @property
    def hits_partial(self) -> int:
        return self._c_partial.value

    def match_full(self, akey, S: int, h_all: int):
        ent = self.full.get((akey, S, h_all))
        if ent is not None:
            self.full.move_to_end((akey, S, h_all))
            self._c_full.inc()
        return ent

    def match_prefix(self, akey, hashes: List[int]) -> List[int]:
        """Longest run of cached full-page blocks for this hash chain."""
        out: List[int] = []
        for h in hashes:
            bid = self.blocks.get((akey, h))
            if bid is None:
                break
            self.blocks.move_to_end((akey, h))
            out.append(bid)
        if out:
            self._c_partial.inc()
        return out

    def insert_block(self, alloc: BlockAllocator, akey, h: int, bid: int):
        key = (akey, h)
        if key in self.blocks:
            self.blocks.move_to_end(key)
            return
        alloc.incref(bid)
        self.blocks[key] = bid

    def insert_full(self, alloc: BlockAllocator, akey, S: int, h_all: int,
                    bids: List[int], logits: np.ndarray):
        key = (akey, S, h_all)
        if key in self.full:
            self.full.move_to_end(key)
            return
        for b in bids:
            alloc.incref(b)
        self.full[key] = (tuple(bids), logits)

    def evict_one(self, alloc: BlockAllocator) -> bool:
        """Drop the LRU entry (full tier first); True if anything dropped."""
        if self.full:
            _, (bids, _) = self.full.popitem(last=False)
            for b in bids:
                alloc.decref(b)
            return True
        if self.blocks:
            _, bid = self.blocks.popitem(last=False)
            alloc.decref(bid)
            return True
        return False

    def clear(self, alloc: BlockAllocator):
        while self.evict_one(alloc):
            pass


@dataclass
class _PagedSlot(_Slot):
    akey: tuple = ()
    nb_worst: int = 0  # worst-case table entries this request may own
    nb_entries: int = 0  # table entries currently owned
    page_hashes: List[int] = field(default_factory=list)
    full_hash: int = 0
    prefill_logits: Optional[np.ndarray] = None  # (1, 1, V) host copy


class PagedScheduler(Scheduler):
    """Continuous batching over a paged block pool instead of slot rows.

    Drop-in for `Scheduler` (same submit/step/run surface, token-exact at
    fp32 greedy) with admission gated on free BLOCKS rather than free
    slots alone: short requests stop paying for the long ones' headroom.

    kv_quant: 'int8'/'fp8' stores KV blocks quantized (4x/4x smaller than
    fp32) with per-token scales; dequantization happens at the attention
    gather. prefix_cache=False disables cross-request sharing (every
    admission prefills cold) without touching the paging itself.
    """

    _sched_kind = "paged"

    def __init__(self, engine, *, num_slots: int, num_blocks: int, page: int,
                 max_len: int, kv_quant: Optional[str] = None,
                 prefix_cache: bool = True, stream=None,
                 prefill_bucket: Optional[int] = None,
                 obs: Optional[MetricsRegistry] = None):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if page < 1 or max_len % page != 0:
            raise ValueError(f"max_len {max_len} must be a multiple of the "
                             f"page size {page}")
        cfg = engine.cfg
        for g in cfg.groups:
            for s in g.slots:
                if s.kind != "attn" or s.cross_attn:
                    raise ValueError(
                        "PagedScheduler accepts self-attention slots only "
                        "(kind='attn', full, windowed or latent, with a "
                        f"dense or MoE FFN); got kind={s.kind!r} "
                        f"cross_attn={s.cross_attn}")
                if s.window is not None and min(s.window, max_len) % page:
                    raise ValueError(
                        f"windowed slot ring {min(s.window, max_len)} must "
                        f"be a multiple of the page size {page}")
        if prefill_bucket is not None:
            if not self.supports_bucketing(cfg):
                raise ValueError("prefill_bucket requires full-attention "
                                 "slots (same contract as Scheduler)")
            if prefill_bucket % page != 0:
                raise ValueError("prefill_bucket must be a multiple of the "
                                 "page size (pages are the unit of insert)")
        self.engine = engine
        self.num_slots = num_slots
        self.max_len = max_len
        self.stream = stream
        self.prefill_bucket = prefill_bucket
        self.page = page
        self.nb_max = max_len // page
        self.kv_quant = kv_quant
        self._init_obs(obs)  # before PrefixCache: its counters land here
        self._windowed = any(s.window is not None
                             for g in cfg.groups for s in g.slots)
        # ring caches fold positions into a modular layout - block content
        # depends on the full trajectory, not the prefix, so sharing and
        # extend are full-attention-only; windowed configs run cold.
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(obs=self.obs) if prefix_cache and not self._windowed
            else None)
        self._prefix_fill = True  # publication gate (admission ladder)
        self._c_cold = self.obs.counter("serve_prefix_hits_total",
                                        tier="cold")
        self._g_free_blocks = self.obs.gauge("kv_free_blocks")
        self._g_reserved_blocks = self.obs.gauge("kv_reserved_blocks")
        self.obs.add_derived(
            "prefix_hit_ratio_full",
            lambda: self._prefix_hit_ratio("full_hits"))
        self.obs.add_derived(
            "prefix_hit_ratio_partial",
            lambda: self._prefix_hit_ratio("partial_hits"))
        self.alloc = BlockAllocator(num_blocks)
        self.pool = engine.init_paged_pool(num_blocks, page, kv_quant)
        self.tables = np.zeros((num_slots, self.nb_max), np.int32)
        self._reserved = 0  # future allocate-on-write budget of live slots
        if self._windowed:
            # every request allocates the same fixed cover at admission:
            # the largest per-slot ring (full slots would need nb_max)
            self._nbl_windowed = max(
                (min(s.window, max_len) if s.window is not None
                 else max_len) // page
                for g in cfg.groups for s in g.slots)
        self.slots: List[Optional[_PagedSlot]] = [None] * num_slots
        self.queue = deque()
        self.completions: Dict[int, Completion] = {}
        self._next_id = 0
        self._ticks = 0
        self._tok = np.zeros((num_slots,), np.int32)
        self._pos = np.zeros((num_slots,), np.int32)
        self._task = np.zeros((num_slots,), np.int32)

    @property
    def stats(self) -> dict:
        """Read-only view of admission-tier counts. The PrefixCache's own
        match counters are the single source of truth for hits; this dict
        is kept for pool_report()/test compatibility."""
        return {
            "full_hits": self.prefix.hits_full if self.prefix else 0,
            "partial_hits": self.prefix.hits_partial if self.prefix else 0,
            "cold": self._c_cold.value,
        }

    def _prefix_hit_ratio(self, key: str) -> float:
        s = self.stats
        tot = s["full_hits"] + s["partial_hits"] + s["cold"]
        return s[key] / tot if tot else 0.0

    def set_prefix_fill(self, on: bool) -> None:
        """Gate prefix-cache PUBLICATION (the admission ladder's first
        rung). Existing entries keep serving hits and keep their LRU
        eviction - only the spend side stops: retiring requests no longer
        pin their prompt blocks, so the pool drains toward in-flight work
        instead of speculative reuse."""
        if self.prefix is None or on == self._prefix_fill:
            return
        self._prefix_fill = on
        self.obs.event("prefix_fill", sched=self._sched_kind, enabled=on)

    # -- sizing -------------------------------------------------------------

    def _nb_worst(self, S: int, max_new: int, P: int) -> int:
        """Worst-case table entries a request may own: its page-aligned
        prefill cover plus every decode write through its token budget."""
        if self._windowed:
            return self._nbl_windowed
        return max(P // self.page, -(-(S + max_new) // self.page))

    def _padded_len(self, S: int) -> int:
        b = self.prefill_bucket if self.prefill_bucket else self.page
        return min(-(-S // b) * b, self.max_len)

    def submit(self, req: Request) -> int:
        S = int(np.asarray(req.prompt).shape[-1])
        nb_worst = self._nb_worst(S, req.max_new_tokens, self._padded_len(S))
        if nb_worst > self.alloc.num_blocks - 1:
            raise ValueError(
                f"request needs {nb_worst} blocks but the pool only has "
                f"{self.alloc.num_blocks - 1} allocatable blocks")
        return super().submit(req)

    # -- prefix hashing -----------------------------------------------------

    def _hash_chain(self, prompt: np.ndarray) -> Tuple[List[int], int]:
        """Chained per-page hashes (position-binding: page j's hash folds
        in page j-1's) plus the whole-prompt hash incl. the partial tail."""
        hs: List[int] = []
        h = 0
        n_full = len(prompt) // self.page
        for j in range(n_full):
            h = hash((h, prompt[j * self.page:(j + 1) * self.page].tobytes()))
            hs.append(h)
        tail = prompt[n_full * self.page:]
        h_all = hash((h, tail.tobytes())) if len(tail) else h
        return hs, h_all

    def _ensure_free(self, need: int):
        """Evict prefix-cache entries until `need` blocks are allocatable
        over and above the live slots' reservations."""
        while self.alloc.num_free - self._reserved < need:
            if self.prefix is None or not self.prefix.evict_one(self.alloc):
                raise BlockPoolFullError(
                    f"need {need} blocks, "
                    f"{self.alloc.num_free - self._reserved} available "
                    f"after reservations")

    # -- admission ----------------------------------------------------------

    def _admit_one(self, slot_idx: int, rid: int, req: Request,
                   submit_t: float) -> str:
        row = req.task_id
        if req.adapter is not None:
            row = self.engine.acquire_adapter(req.adapter)  # pins the row
        try:
            kind = self._admit_paged(slot_idx, rid, req, submit_t, row)
        except BlockPoolFullError:
            if req.adapter is not None:
                self.engine.release_adapter(req.adapter)
            raise
        queue_s = time.perf_counter() - submit_t
        self._m_queue_s.observe(queue_s)
        return kind

    def _admit_paged(self, slot_idx: int, rid: int, req: Request,
                     submit_t: float, row: int) -> str:
        """Prefill (or replay) the prompt into the pool; returns the hit
        kind."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        S = len(prompt)
        page = self.page
        nb_cov = -(-S // page)  # blocks covering the true prompt
        P = S if self._windowed else self._padded_len(S)
        nb_worst = self._nb_worst(S, req.max_new_tokens, P)
        # hot-swap adapters can be republished with new weights mid-stream,
        # which would silently stale any KV cached under the name - named
        # requests therefore never share KV (static task rows are immutable)
        cacheable = self.prefix is not None and req.adapter is None
        akey = ("task", row)
        hashes, h_all = self._hash_chain(prompt) if cacheable else ([], 0)

        tr = self.obs.tracer.get(rid)
        st = _PagedSlot(request_id=rid, req=req,
                        rng=(jax.random.PRNGKey(
                            req.seed if req.seed is not None else rid)
                            if req.top_k else None),
                        pos=S, row=row, submit_t=submit_t, akey=akey,
                        nb_worst=nb_worst, page_hashes=hashes,
                        full_hash=h_all, trace=tr)
        tbl = self.tables[slot_idx]

        ent = self.prefix.match_full(akey, S, h_all) if cacheable else None
        if ent is not None:
            # ---- full hit: no forward pass at all ----
            bids, logits = list(ent[0]), ent[1]
            for b in bids:
                self.alloc.incref(b)
            try:
                fork = 1 if S % page else 0
                self._ensure_free(fork + nb_worst - nb_cov)
            except BlockPoolFullError:
                for b in bids:
                    self.alloc.decref(b)
                raise
            if S % page:
                # the tail block is partially filled: the first decode
                # write lands inside it, so the writer forks it COW
                dst = self.alloc.alloc()
                self.pool = self.engine.copy_block(self.pool, bids[-1], dst)
                self.alloc.decref(bids[-1])
                bids[-1] = dst
            tbl[:nb_cov] = bids
            st.nb_entries = nb_cov
            st.prefill_logits = logits
            hit_kind = "full_hit"  # counted by PrefixCache.match_full
        else:
            m_bids: List[int] = []
            if cacheable and S > page:
                m_bids = self.prefix.match_prefix(
                    akey, hashes[:(S - 1) // page])  # keep suffix non-empty
            m = len(m_bids)
            if m:
                # ---- partial hit: prefill only the suffix, in place ----
                for b in m_bids:
                    self.alloc.incref(b)
                try:
                    self._ensure_free(nb_worst - m)
                except BlockPoolFullError:
                    for b in m_bids:
                        self.alloc.decref(b)
                    raise
                tbl[:m] = m_bids
                for j in range(m, nb_cov):
                    tbl[j] = self.alloc.alloc()
                st.nb_entries = nb_cov
                sfx = prompt[m * page:]
                padded = (nb_cov - m) * page
                if padded > len(sfx):
                    sfx = np.pad(sfx, (0, padded - len(sfx)))
                logits, self.pool = self.engine.paged_extend(
                    self.pool, sfx.reshape(1, -1),
                    self.tables[slot_idx:slot_idx + 1],
                    start=m * page, kv_len=S,
                    last_pos=S - m * page - 1,
                    task_ids=np.asarray([row]))
                with self._span("prefill_wait"):
                    st.prefill_logits = np.asarray(logits[:, -1:])
                hit_kind = "partial_hit"  # counted by match_prefix
            else:
                # ---- cold: prefill the page-aligned prompt, insert ----
                self._ensure_free(nb_worst)
                nbl = (self._nbl_windowed if self._windowed
                       else P // page)
                for j in range(nbl):
                    tbl[j] = self.alloc.alloc()
                st.nb_entries = nbl
                toks = prompt.reshape(1, -1)
                if P > S:
                    toks = np.pad(toks, ((0, 0), (0, P - S)))
                cache_len = self.max_len if self._windowed else P
                logits, fresh = self.engine.prefill(
                    toks, cache_len, task_ids=np.asarray([row]),
                    last_pos=None if (self._windowed or P == S) else S - 1)
                self.pool = self.engine.paged_insert(
                    self.pool, fresh, tbl[:nbl])
                with self._span("prefill_wait"):
                    st.prefill_logits = np.asarray(logits[:, -1:])
                self._c_cold.inc()
                hit_kind = "cold"

        # marks land only on success: a deferred admission (pool full)
        # leaves no admit mark, so traces record exactly one admit
        tr.mark("admit", slot=slot_idx, row=row, adapter=req.adapter,
                queue_s=time.perf_counter() - submit_t)
        tr.mark("prefill", kind=hit_kind, blocks=st.nb_entries)
        self._reserved += st.nb_worst - st.nb_entries
        self.slots[slot_idx] = st
        if st.req.top_k and st.rng is not None:
            st.next_tok = self._sample_one(
                jnp.asarray(st.prefill_logits), st)
        else:
            # greedy on the host copy: argmax ties break identically to
            # jnp's, and skipping the device round-trip keeps warm-hit
            # admission (stored-logit replay) off the dispatch path
            st.next_tok = int(st.prefill_logits[0, -1].argmax())
        self._task[slot_idx] = row
        if not self._emit(slot_idx, st, st.next_tok):
            self._tok[slot_idx] = st.next_tok
            self._pos[slot_idx] = st.pos
        return hit_kind

    # -- retirement ---------------------------------------------------------

    def _retire(self, slot_idx: int, st: _PagedSlot, reason: str):
        tbl = self.tables[slot_idx]
        if (self.prefix is not None and self._prefix_fill
                and st.req.adapter is None
                and reason != "error" and st.prefill_logits is not None):
            # publish the prompt's blocks before dropping our references:
            # full pages into the chain tier, the whole cover (incl. the
            # partial tail and the stored logits) into the full tier
            S = int(np.asarray(st.req.prompt).shape[-1])
            for j, h in enumerate(st.page_hashes):
                self.prefix.insert_block(self.alloc, st.akey, h, int(tbl[j]))
            nb_cov = -(-S // self.page)
            self.prefix.insert_full(
                self.alloc, st.akey, S, st.full_hash,
                [int(b) for b in tbl[:nb_cov]], st.prefill_logits)
        self._reserved -= st.nb_worst - st.nb_entries
        for j in range(self.nb_max):
            if tbl[j]:
                self.alloc.decref(int(tbl[j]))
                tbl[j] = 0
        super()._retire(slot_idx, st, reason)

    # -- the tick -----------------------------------------------------------

    # defer on block exhaustion too: admission retries after the next
    # retirement releases capacity (base _do_admissions, FIFO preserved)
    _defer_errors = (BankFullError, BlockPoolFullError)

    def _step_impl(self) -> int:
        self._do_admissions()
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return 0
        with self._span("plan"):
            # allocate-on-write: hand a fresh page to every slot whose next
            # write crosses a page boundary. The reservation invariant
            # (free >= reserved, one unit released per allocation) makes
            # this infallible mid-decode - admission already paid for the
            # worst case.
            for i in occupied:
                st = self.slots[i]
                p = int(self._pos[i])
                j = p // self.page
                if (p % self.page == 0 and j < st.nb_worst
                        and not self.tables[i, j]):
                    self.tables[i, j] = self.alloc.alloc()
                    st.nb_entries += 1
                    self._reserved -= 1
            tok = jnp.asarray(self._tok[:, None])
            pos = jnp.asarray(self._pos)
            task = self._task.copy()
        with self._span("decode"):
            logits, self.pool = self.engine.paged_decode_step(
                self.pool, tok, pos, self.tables, task_ids=task)
        self._ticks += 1
        return self._sample_and_emit(occupied, logits)

    def _post_tick(self) -> None:
        self._g_free_blocks.set(self.alloc.num_free)
        self._g_reserved_blocks.set(self._reserved)
        super()._post_tick()

    # -- accounting ---------------------------------------------------------

    def pool_report(self) -> dict:
        """Live pool accounting for benches/tests."""
        live = self.alloc.num_blocks - 1 - self.alloc.num_free
        return {
            "num_blocks": self.alloc.num_blocks - 1,
            "live_blocks": live,
            "free_blocks": self.alloc.num_free,
            "reserved_blocks": self._reserved,
            "prefix_block_entries": (len(self.prefix.blocks)
                                     if self.prefix else 0),
            "prefix_full_entries": (len(self.prefix.full)
                                    if self.prefix else 0),
            **self.stats,
        }
