"""Serving engine: batched prefill + greedy/top-k decode with KV caches,
and multi-task Hadamard serving (one frozen backbone, per-request adapters).

The multi-task path is the deployment story the paper's §5 analysis points
at: adapters are 2*L*d floats per task, so a bank of hundreds of tasks is
megabytes; requests carrying different task ids batch together and each
token is transformed by its own (w, b) - the Hadamard analogue of
multi-LoRA serving.

Sharded serving: construct the engine inside `use_mesh(mesh)` and it
places the (folded/bank) params per `params_shardings` and re-activates
the mesh around every prefill/decode trace, so one model-sharded backbone
serves all tasks. Without a mesh everything stays single-device.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import ModelCfg
from repro.core.hadamard import build_bank, fold_adapter, select_tasks
from repro.dist.api import current_mesh, use_mesh
from repro.dist.sharding import (paged_cache_shardings, params_shardings,
                                 slot_cache_shardings)
from repro.models import model as M


def sample_greedy(logits):
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


def check_temperature(temperature) -> float:
    """Validate a sampling temperature at request submission: 0 means
    greedy (explicitly), anything negative or non-finite is a caller bug
    worth rejecting before the request ever reaches a decode tick."""
    t = float(temperature)
    if not np.isfinite(t) or t < 0:
        raise ValueError(
            f"temperature must be finite and >= 0 (got {temperature!r}); "
            "temperature=0 decodes greedily")
    return t


def sample_topk(logits, rng, k: int = 40, temperature: float = 1.0):
    """Top-k sampling; `temperature <= 0` is explicit argmax. (It used to
    be clamped to 1e-6, so temperature=0 silently became a 1e6x logit
    blow-up - numerically argmax-ish at best, inf/nan at worst - instead
    of the greedy decode the caller asked for.)"""
    if temperature <= 0:
        return sample_greedy(logits)
    lg = logits[:, -1] / temperature
    top, idx = jax.lax.top_k(lg, k)
    choice = jax.random.categorical(rng, top)
    return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)


class ServeEngine:
    """Greedy/top-k generation over any decoder-family config.

    quant: None keeps the params as given; "int8"/"fp8" quantizes the
    frozen backbone's matmul projections at placement time (after any
    adapter folding), so device memory holds 1 byte/weight and decode
    matmuls run through the fused dequant kernel. A tree that already
    carries QTensor leaves (a quantized checkpoint restored cold) passes
    through untouched - quantize_tree is idempotent.
    """

    def __init__(self, cfg: ModelCfg, params, *, fold: bool = False,
                 quant: Optional[str] = None):
        if fold and cfg.adapter.kind == "hadamard":
            params = fold_adapter(params, cfg)
        if quant:
            from repro.quant import quantize_tree  # deferred: light path

            params = quantize_tree(params, mode=quant)
        self.quant = quant
        self.cfg = cfg
        self.mesh = current_mesh()
        self.params = self._place(params)
        # Each jitted fn bumps trace_counts when its python body runs (i.e.
        # on compile), so schedulers can detect mid-serve retraces - same
        # protocol as MultiTaskEngine. The closures bind the DICT, not the
        # attribute: MultiTaskEngine replaces self.trace_counts with its own
        # dict for the task-gather jits, and these legacy lock-step jits
        # must not leak compiles into that one (its contract is one count
        # per scheduler-tick shape, asserted by the registry/sparse tests).
        self.trace_counts = {"prefill": 0, "decode": 0, "decode_paged": 0,
                             "verify": 0, "verify_paged": 0}
        tc = self.trace_counts

        def _pf(p, toks, cl):
            tc["prefill"] += 1
            return M.prefill_lm(p, cfg, toks, cache_len=cl)

        def _pfat(p, toks, cl, lp):
            tc["prefill"] += 1
            return M.prefill_lm(p, cfg, toks, cache_len=cl, last_pos=lp)

        def _dc(p, caches, tok, pos):
            tc["decode"] += 1
            return M.decode_lm(p, cfg, caches, tok, pos)

        # -- paged-pool variants (serving/paged.py). The pool tree is the
        # single largest live allocation, so every mutation donates it.
        def _pdc(p, pool, tok, pos, tbl):
            tc["decode_paged"] += 1
            return M.decode_lm_paged(p, cfg, pool, tok, pos, tbl)

        def _pext(p, pool, toks, tbl, start, kvl, lp):
            return M.extend_lm(p, cfg, pool, toks, tbl, start, kvl, lp)

        # -- speculative verify: score k+1 tokens per row in ONE forward
        # (serving/spec.py). Same donation discipline as decode.
        def _vf(p, caches, toks, pos):
            tc["verify"] += 1
            return M.verify_lm(p, cfg, caches, toks, pos)

        def _vfp(p, pool, toks, pos, tbl):
            tc["verify_paged"] += 1
            return M.verify_lm_paged(p, cfg, pool, toks, pos, tbl)

        self._prefill = jax.jit(_pf, static_argnums=(2,))
        self._prefill_at = jax.jit(_pfat, static_argnums=(2,))
        self._decode = jax.jit(_dc, donate_argnums=(1,))
        self._decode_paged = jax.jit(_pdc, donate_argnums=(1,))
        self._extend = jax.jit(_pext, donate_argnums=(1,))
        self._verify = jax.jit(_vf, donate_argnums=(1,))
        self._verify_paged = jax.jit(_vfp, donate_argnums=(1,))
        self._paged_insert_jit = jax.jit(self._paged_insert_impl,
                                         donate_argnums=(0,))
        self._copy_block_jit = jax.jit(self._copy_block_impl,
                                       donate_argnums=(0,))

    # -- mesh plumbing ------------------------------------------------------

    def _place(self, params):
        """Shard params over the construction-time mesh (no-op without one)."""
        if self.mesh is None:
            return params
        return jax.device_put(
            params, params_shardings(params, self.cfg, self.mesh))

    def _mesh_ctx(self):
        """Re-activate the engine's mesh so jit traces see its constraints
        (use_mesh(None) is a no-op for meshless engines)."""
        return use_mesh(self.mesh)

    # -- scheduler hooks (continuous batching, see serving/scheduler.py) ----

    def prefill(self, tokens, cache_len: int, task_ids=None, last_pos=None):
        """(last-token logits, fresh caches) for same-length prompts.
        task_ids is accepted for interface parity and ignored here;
        last_pos selects which position's logits to return (prompt-length
        bucketing: right-padded prompts pass their true last index)."""
        with self._mesh_ctx():
            if last_pos is None:
                return self._prefill(self.params, jnp.asarray(tokens),
                                     int(cache_len))
            return self._prefill_at(self.params, jnp.asarray(tokens),
                                    int(cache_len), jnp.int32(last_pos))

    def decode_step(self, caches, tok, pos, task_ids=None):
        """One fused decode step. pos may be a scalar or a (B,) vector of
        per-row positions (the scheduler's per-slot tick)."""
        with self._mesh_ctx():
            return self._decode(self.params, caches, tok, pos)

    def verify_step(self, caches, toks, pos, task_ids=None):
        """Speculative verify: score toks (B, k+1) = [last accepted token,
        k draft tokens] per row in ONE decode-mode forward, writing all
        k+1 cache positions. pos: (B,) absolute position of toks[:, 0].
        Rejected draft writes are stale-but-harmless: the next tick's
        write range starts at the first rejected position and overwrites
        them before any mask admits them (see models.model.verify_lm)."""
        with self._mesh_ctx():
            return self._verify(self.params, caches, jnp.asarray(toks),
                                jnp.asarray(pos, jnp.int32))

    def init_slot_caches(self, num_slots: int, cache_len: int):
        """Zeroed slot-pool caches: row i is slot i's private cache region.
        Under a mesh the pool is placed with the slot dim replicated so
        per-slot admission scatters stay collective-free."""
        caches = M.init_decode_caches(self.cfg, num_slots, cache_len)
        if self.mesh is not None:
            caches = jax.device_put(
                caches, slot_cache_shardings(caches, self.cfg, self.mesh))
        return caches

    # -- paged block pool (serving/paged.py) --------------------------------

    def init_paged_pool(self, num_blocks: int, page: int,
                        kv_quant: Optional[str] = None):
        """Zeroed device block pool: (repeats, num_blocks, page, KH, Dh)
        per attention slot (QTensor leaves under kv_quant; one latent leaf
        of width mla.cache_dim for latent attention, which refuses
        kv_quant). Block 0 is the allocator's reserved null block. Under
        a mesh the pool is placed with the block dim replicated (kv heads
        model-sharded) so host-driven block handoffs never trigger
        collectives."""
        pool = M.init_paged_pool(self.cfg, num_blocks, page, quant=kv_quant)
        if self.mesh is not None:
            pool = jax.device_put(
                pool, paged_cache_shardings(pool, self.cfg, self.mesh))
        return pool

    @staticmethod
    def _paged_insert_impl(pool, fresh, ids):
        """Scatter a freshly prefilled contiguous cache (B=1) into pool
        blocks `ids`. fresh leaves (R, 1, nbl*page, KH, Dh) are repaged to
        (R, nbl, page, KH, Dh), and a latent leaf (R, 1, nbl*page, width)
        to (R, nbl, page, width) alike; QTensor pools quantize per
        page-token on the way in (absmax over Dh - the same
        independent-per-write rule the decode path uses, so
        extend/prefill agree bit-for-bit)."""
        from repro.quant.qtensor import QTensor, is_qtensor, quantize

        def one(dst, src):
            r = src[:, 0]  # (R, S, KH, Dh)
            if is_qtensor(dst):
                page = dst.values.shape[2]
                mode = "int8" if dst.values.dtype == jnp.int8 else "fp8"
                r = r.reshape(r.shape[0], -1, page, *r.shape[2:])
                qt = quantize(r, mode, axis=-1)
                my = ids[:r.shape[1]]  # windowed leaves cover fewer pages
                return QTensor(dst.values.at[:, my].set(qt.values),
                               dst.scales.at[:, my].set(qt.scales))
            page = dst.shape[2]
            r = r.reshape(r.shape[0], -1, page, *r.shape[2:])
            return dst.at[:, ids[:r.shape[1]]].set(r.astype(dst.dtype))

        return jax.tree.map(one, pool, fresh,
                            is_leaf=lambda x: is_qtensor(x))

    @staticmethod
    def _copy_block_impl(pool, src, dst):
        """COW fork: duplicate physical block src into dst on every leaf
        (K/V, QTensor components or the latent)."""
        from repro.quant.qtensor import QTensor, is_qtensor

        def one(leaf):
            if is_qtensor(leaf):
                return QTensor(leaf.values.at[:, dst].set(leaf.values[:, src]),
                               leaf.scales.at[:, dst].set(leaf.scales[:, src]))
            return leaf.at[:, dst].set(leaf[:, src])

        return jax.tree.map(one, pool, is_leaf=lambda x: is_qtensor(x))

    def paged_insert(self, pool, fresh, bids):
        """Write prefilled caches into the pool blocks `bids` (host list;
        its LENGTH is a static shape, bucketed with the prefill lengths)."""
        with self._mesh_ctx():
            return self._paged_insert_jit(pool, fresh,
                                          jnp.asarray(bids, jnp.int32))

    def copy_block(self, pool, src: int, dst: int):
        with self._mesh_ctx():
            return self._copy_block_jit(pool, jnp.int32(src), jnp.int32(dst))

    def paged_decode_step(self, pool, tok, pos, tables, task_ids=None):
        """One fused decode tick against the block pool. tables is the
        host-side (num_slots, nb_max) int32 array - a stable shape, so the
        tick compiles exactly once."""
        with self._mesh_ctx():
            return self._decode_paged(self.params, pool, tok, pos,
                                      jnp.asarray(tables))

    def paged_verify_step(self, pool, toks, pos, tables, task_ids=None):
        """Speculative verify against the block pool: toks (B, k+1), pos
        (B,) absolute position of toks[:, 0]. All k+1 positions are
        written into each row's pages (the spec scheduler pre-allocates
        every page the write range can touch); masks are per-query causal
        so earlier queries never see later draft writes."""
        with self._mesh_ctx():
            return self._verify_paged(self.params, pool, jnp.asarray(toks),
                                      jnp.asarray(pos, jnp.int32),
                                      jnp.asarray(tables))

    def paged_extend(self, pool, tokens, tables, start, kv_len, last_pos,
                     task_ids=None):
        """Prefill a prompt suffix directly into pool blocks (prefix-cache
        partial hit): `tokens` (1, S_pad) right-padded suffix, `start` its
        absolute offset, `kv_len` the true total prompt length, `last_pos`
        the in-suffix index of the true last token. Retraces per padded
        suffix length (bucketed by the scheduler)."""
        with self._mesh_ctx():
            return self._extend(self.params, pool, jnp.asarray(tokens),
                                jnp.asarray(tables), jnp.int32(start),
                                jnp.int32(kv_len), jnp.int32(last_pos))

    # -- sampling -----------------------------------------------------------

    def _sample(self, logits, rng, top_k: int):
        """One sampling decision; returns (token, advanced rng)."""
        if top_k and rng is not None:
            rng, sub = jax.random.split(rng)
            return sample_topk(logits, sub, k=top_k), rng
        return sample_greedy(logits), rng

    def generate(self, requests, max_new_tokens: Optional[int] = None,
                 rng: Optional[jax.Array] = None, top_k: int = 0):
        """Unified generation entry point.

        Two input forms:
          * an int array (B, S) of same-length prompts + `max_new_tokens`:
            the classic lock-step batch. Returns (B, max_new_tokens) - one
            row per prompt, every row decoded to the full budget.
          * a list of `serving.Request`s (same-length prompts): per-request
            budgets, sampling params (top_k/temperature/seed) and - on
            MultiTaskEngine - task_id/adapter are honoured. Returns a list
            of per-request token arrays, each truncated at its own
            max_new_tokens and (when eos_id is set) at the first EOS
            (inclusive). A call-level `rng` switches the whole batch to
            call-level sampling (`top_k` applies to every row) - the
            legacy shims delegate through this path for exact parity.

        Mixed prompt lengths / streaming / continuous arrival belong to
        the schedulers: `serving.make_scheduler(engine, ServingConfig())`.
        """
        if not isinstance(requests, (list, tuple)):
            if max_new_tokens is None:
                raise ValueError("array input requires max_new_tokens")
            return self._lockstep(np.asarray(requests), int(max_new_tokens),
                                  rng, top_k)
        reqs = list(requests)
        if not reqs:
            return []
        for r in reqs:
            check_temperature(r.temperature)
        prompts = [np.asarray(r.prompt, np.int32).reshape(-1) for r in reqs]
        if len({p.shape[0] for p in prompts}) != 1:
            raise ValueError(
                "generate(list[Request]) batches lock-step and needs "
                "same-length prompts; use serving.make_scheduler for "
                "heterogeneous lengths")
        tokens = np.stack(prompts)
        budget = max(r.max_new_tokens for r in reqs)
        if max_new_tokens is not None:
            budget = min(budget, int(max_new_tokens))
        return self._generate_rows(tokens, reqs, budget, rng, top_k)

    def _generate_rows(self, tokens, reqs, budget, rng, top_k):
        """Request-list path. The base engine has a single param tree, so
        per-request adapters are a MultiTaskEngine feature (override)."""
        if any(r.task_id or r.adapter is not None for r in reqs):
            raise ValueError(
                "per-request task_id/adapter requires a MultiTaskEngine")
        return self._decode_rows(tokens, reqs, budget, rng, top_k)

    def _decode_rows(self, tokens, reqs, budget, rng, top_k):
        """Lock-step decode with per-request sampling + truncation."""
        if rng is not None:  # call-level sampling (legacy-shim parity)
            out = self._lockstep(tokens, budget, rng, top_k)
            return self._truncate(out, reqs)
        keys = [jax.random.PRNGKey(r.seed if r.seed is not None else i)
                if r.top_k else None for i, r in enumerate(reqs)]

        def pick(logits):
            toks = np.asarray(sample_greedy(logits))
            for i, r in enumerate(reqs):
                if r.top_k:  # per-row rng stream, scheduler-compatible
                    keys[i], sub = jax.random.split(keys[i])
                    toks[i] = int(sample_topk(logits[i:i + 1], sub,
                                              k=r.top_k,
                                              temperature=r.temperature)[0])
            return jnp.asarray(toks, jnp.int32)

        out = self._lockstep(tokens, budget, None, 0, pick=pick)
        return self._truncate(out, reqs)

    @staticmethod
    def _truncate(out, reqs):
        res = []
        for i, r in enumerate(reqs):
            row = np.asarray(out[i, :r.max_new_tokens])
            if r.eos_id is not None:
                hits = np.flatnonzero(row == r.eos_id)
                if hits.size:
                    row = row[:hits[0] + 1]
            res.append(row)
        return res

    def _lockstep(self, tokens: np.ndarray, max_new_tokens: int,
                  rng: Optional[jax.Array], top_k: int, pick=None):
        B, S = tokens.shape
        cache_len = S + max_new_tokens
        with self._mesh_ctx():
            logits, caches = self._prefill(
                self.params, jnp.asarray(tokens), cache_len)
            out = []
            # the first post-prefill token goes through the same sampling
            # path as every later one (greedy only when sampling is off)
            if pick is None:
                tok, rng = self._sample(logits, rng, top_k)
            else:
                tok = pick(logits)
            for i in range(max_new_tokens):
                out.append(tok)
                logits, caches = self._decode(
                    self.params, caches, tok[:, None], jnp.int32(S + i))
                if pick is None:
                    tok, rng = self._sample(logits, rng, top_k)
                else:
                    tok = pick(logits)
        return np.stack([np.asarray(t) for t in out], axis=1)


class MultiTaskEngine(ServeEngine):
    """One frozen backbone + a bank of per-task Hadamard adapters.

    `tasks` is either a list of per-task param trees sharing every
    non-adapter leaf (static bank, frozen at construction) or an
    `AdapterBank` (hot-swappable: rows are inserted/evicted at runtime by
    name through its registry - see serving/registry.py). Each generate()
    call takes per-request task ids (bank rows); adapters are gathered per
    request and broadcast over the sequence inside apply_hadamard. Adapter
    leaves are replicated by the sharding rules, so the gather is
    collective-free under a mesh.

    Hot-swap contract: the bank tree never changes shape (row writes are
    in-place donated scatters), so `trace_counts` stays at one compile per
    tick shape across any number of swaps - asserted by the registry tests.
    """

    def __init__(self, cfg: ModelCfg, tasks, *, quant: Optional[str] = None):
        from repro.serving.registry import AdapterBank  # cycle-free import

        self.adapter_bank = tasks if isinstance(tasks, AdapterBank) else None
        tree = (self.adapter_bank.tree if self.adapter_bank is not None
                else build_bank(tasks))
        # quantize_tree touches only backbone matmul leaves: the stacked
        # adapter rows and tuned norms stay fp32, so hot-swap row inserts
        # and the per-request bank gather are untouched by quantization
        super().__init__(cfg, tree, fold=False, quant=quant)
        if self.adapter_bank is not None:
            # the bank owns the (mesh-placed) live tree from here on: row
            # inserts donate and rebind it, so the engine must re-read it
            # every call instead of capturing this placement
            self.adapter_bank.attach(self.params, self.mesh)
            self.params = None
        else:
            self._static_bank = self.params
        # Scheduler-tick variants: the bank gather happens INSIDE the jit so
        # a fresh mix of task ids each tick re-gathers without re-placing
        # params (the gather is collective-free: adapters are replicated).
        # The python bodies bump trace_counts, making retraces observable.
        self.trace_counts = {"prefill": 0, "decode": 0, "decode_paged": 0,
                             "verify": 0, "verify_paged": 0}

        def _pf(bank, toks, tids, cl, lp):
            self.trace_counts["prefill"] += 1
            return M.prefill_lm(select_tasks(bank, tids), cfg, toks,
                                cache_len=cl, last_pos=lp)

        def _dc(bank, caches, tok, pos, tids):
            self.trace_counts["decode"] += 1
            return M.decode_lm(select_tasks(bank, tids), cfg, caches, tok,
                               pos)

        def _pdc(bank, pool, tok, pos, tbl, tids):
            self.trace_counts["decode_paged"] += 1
            return M.decode_lm_paged(select_tasks(bank, tids), cfg, pool,
                                     tok, pos, tbl)

        def _pext(bank, pool, toks, tbl, start, kvl, lp, tids):
            return M.extend_lm(select_tasks(bank, tids), cfg, pool, toks,
                               tbl, start, kvl, lp)

        def _vf(bank, caches, toks, pos, tids):
            self.trace_counts["verify"] += 1
            return M.verify_lm(select_tasks(bank, tids), cfg, caches, toks,
                               pos)

        def _vfp(bank, pool, toks, pos, tbl, tids):
            self.trace_counts["verify_paged"] += 1
            return M.verify_lm_paged(select_tasks(bank, tids), cfg, pool,
                                     toks, pos, tbl)

        self._prefill_tasks = jax.jit(_pf, static_argnums=(3,))
        self._decode_tasks = jax.jit(_dc, donate_argnums=(1,))
        self._decode_paged_tasks = jax.jit(_pdc, donate_argnums=(1,))
        self._extend_tasks = jax.jit(_pext, donate_argnums=(1,))
        self._verify_tasks = jax.jit(_vf, donate_argnums=(1,))
        self._verify_paged_tasks = jax.jit(_vfp, donate_argnums=(1,))

    @property
    def bank(self):
        """The live bank tree (re-read from the AdapterBank each call:
        hot-swap inserts donate the previous tree)."""
        return (self.adapter_bank.tree if self.adapter_bank is not None
                else self._static_bank)

    # -- adapter-name resolution (scheduler admission) ----------------------

    def has_adapter(self, name: str) -> bool:
        return (self.adapter_bank is not None
                and (self.adapter_bank.row_of(name) is not None
                     or name in self.adapter_bank.registry))

    def acquire_adapter(self, name: str) -> int:
        """name -> pinned bank row (loading from the registry on a miss)."""
        if self.adapter_bank is None:
            raise ValueError(
                "engine has a static bank; named-adapter requests need an "
                "AdapterBank (MultiTaskEngine(cfg, AdapterBank(...)))")
        return self.adapter_bank.acquire(name)

    def release_adapter(self, name: str) -> None:
        if self.adapter_bank is not None:
            self.adapter_bank.release(name)

    def prefill(self, tokens, cache_len: int, task_ids=None, last_pos=None):
        if task_ids is None:
            # the bank's stacked adapter leaves are not runnable params
            raise ValueError("MultiTaskEngine.prefill requires task_ids")
        toks = jnp.asarray(tokens)
        if last_pos is None:
            last_pos = toks.shape[1] - 1
        with self._mesh_ctx():
            return self._prefill_tasks(
                self.bank, toks, jnp.asarray(task_ids, jnp.int32),
                int(cache_len), jnp.int32(last_pos))

    def decode_step(self, caches, tok, pos, task_ids=None):
        if task_ids is None:
            raise ValueError("MultiTaskEngine.decode_step requires task_ids")
        with self._mesh_ctx():
            return self._decode_tasks(
                self.bank, caches, tok, pos, jnp.asarray(task_ids, jnp.int32))

    def verify_step(self, caches, toks, pos, task_ids=None):
        if task_ids is None:
            raise ValueError("MultiTaskEngine.verify_step requires task_ids")
        with self._mesh_ctx():
            return self._verify_tasks(
                self.bank, caches, jnp.asarray(toks),
                jnp.asarray(pos, jnp.int32),
                jnp.asarray(task_ids, jnp.int32))

    def paged_verify_step(self, pool, toks, pos, tables, task_ids=None):
        if task_ids is None:
            raise ValueError(
                "MultiTaskEngine.paged_verify_step requires task_ids")
        with self._mesh_ctx():
            return self._verify_paged_tasks(
                self.bank, pool, jnp.asarray(toks),
                jnp.asarray(pos, jnp.int32), jnp.asarray(tables),
                jnp.asarray(task_ids, jnp.int32))

    def paged_decode_step(self, pool, tok, pos, tables, task_ids=None):
        if task_ids is None:
            raise ValueError(
                "MultiTaskEngine.paged_decode_step requires task_ids")
        with self._mesh_ctx():
            return self._decode_paged_tasks(
                self.bank, pool, tok, pos, jnp.asarray(tables),
                jnp.asarray(task_ids, jnp.int32))

    def paged_extend(self, pool, tokens, tables, start, kv_len, last_pos,
                     task_ids=None):
        if task_ids is None:
            raise ValueError("MultiTaskEngine.paged_extend requires task_ids")
        with self._mesh_ctx():
            return self._extend_tasks(
                self.bank, pool, jnp.asarray(tokens), jnp.asarray(tables),
                jnp.int32(start), jnp.int32(kv_len), jnp.int32(last_pos),
                jnp.asarray(task_ids, jnp.int32))

    def _generate_rows(self, tokens, reqs, budget, rng, top_k):
        """Request-list path with per-request adapters: resolve every name
        to a bank row up front (pin unique names once, so no row displaces
        another mid-batch), swap in the per-row selected params for the
        lock-step run, release in finally - a mid-resolution BankFullError
        or KeyError must not leak pins and wedge the bank."""
        uniq = list(dict.fromkeys(
            r.adapter for r in reqs if r.adapter is not None))
        if uniq and self.adapter_bank is None:
            raise ValueError(
                "named-adapter requests need an AdapterBank "
                "(MultiTaskEngine(cfg, AdapterBank(...)))")
        acquired = []
        try:
            for n in uniq:
                self.adapter_bank.acquire(n)
                acquired.append(n)
            rows = np.asarray(
                [self.adapter_bank.row_of(r.adapter)
                 if r.adapter is not None else r.task_id for r in reqs],
                np.int32)
            saved = self.params
            self.params = select_tasks(self.bank, jnp.asarray(rows))
            try:
                return self._decode_rows(tokens, reqs, budget, rng, top_k)
            finally:
                self.params = saved
        finally:
            for n in acquired:
                self.adapter_bank.release(n)

    # -- deprecated entry points (use generate(list[Request])) --------------

    def generate_for_tasks(self, tokens: np.ndarray, task_ids: np.ndarray,
                           max_new_tokens: int,
                           rng: Optional[jax.Array] = None, top_k: int = 0):
        """Deprecated: `generate(list[Request])` with per-request task_id
        subsumes this. Token-identical delegation (call-level rng keeps the
        exact legacy sampling stream); returns the legacy stacked array."""
        warnings.warn(
            "generate_for_tasks is deprecated; use MultiTaskEngine."
            "generate([Request(..., task_id=...)], ...) instead",
            DeprecationWarning, stacklevel=2)
        rows = np.asarray(task_ids, np.int32)
        saved = self.params
        self.params = select_tasks(self.bank, jnp.asarray(rows))
        try:
            return self._lockstep(np.asarray(tokens), int(max_new_tokens),
                                  rng, top_k)
        finally:
            self.params = saved

    def generate_for_adapters(self, tokens: np.ndarray, names,
                              max_new_tokens: int,
                              rng: Optional[jax.Array] = None, top_k: int = 0):
        """Deprecated: `generate(list[Request])` with per-request `adapter`
        subsumes this (same pin-unique/release discipline)."""
        warnings.warn(
            "generate_for_adapters is deprecated; use MultiTaskEngine."
            "generate([Request(..., adapter=...)], ...) instead",
            DeprecationWarning, stacklevel=2)
        if self.adapter_bank is None:
            raise ValueError("generate_for_adapters needs an AdapterBank")
        from repro.serving.scheduler import Request  # cycle-free at runtime

        tokens = np.asarray(tokens)
        reqs = [Request(prompt=tokens[i], max_new_tokens=int(max_new_tokens),
                        adapter=n) for i, n in enumerate(names)]
        out = self._generate_rows(tokens, reqs, int(max_new_tokens), rng,
                                  top_k)
        return np.stack(out, axis=0)
