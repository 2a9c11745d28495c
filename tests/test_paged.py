"""Paged KV cache (serving/paged.py + kernels paged attention).

Covers the PR's acceptance contract:
  * block allocator: property-tested refcount discipline (no leaks, no
    double frees, refcounts == live readers) over random op sequences
  * paged attention kernel: interpret-mode Pallas vs dense oracle, over
    linear and ring-window masks, fp32 and int8 pools
  * paged decode path: bit-exact fp32 logits vs the contiguous decode
    path, and bounded top-1 agreement under int8 KV blocks
  * prefix cache: warm full hits skip the forward pass and stay
    token-exact; COW tail forks isolate concurrent writers sharing a
    prefix; partial hits extend in place exactly
  * block exhaustion: a pool smaller than the offered load backpressures
    FIFO and still drains every request
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import stacked_groups, tiny_cfg
from repro.common.types import Group, Slot
from repro.kernels import ops, ref
from repro.models import model as M
from repro.quant.qtensor import quantize
from repro.serving import ServingConfig, make_scheduler
from repro.serving.engine import ServeEngine
from repro.serving.paged import (BlockAllocator, BlockPoolFullError,
                                 PagedScheduler, PrefixCache)
from repro.serving.scheduler import Request

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# block allocator: refcount discipline under random op sequences
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       num_blocks=st.integers(min_value=2, max_value=24))
def test_allocator_refcount_discipline(seed, num_blocks):
    """Shadow-model the allocator with a plain dict of refcounts: after
    any op sequence, (a) every live block's refcount matches the model,
    (b) free + live == num_blocks - 1 (block 0 never circulates), and
    (c) exhaustion raises instead of handing out a dup."""
    rng = random.Random(seed)
    alloc = BlockAllocator(num_blocks)
    model = {}  # bid -> refcount
    for _ in range(200):
        op = rng.choice(("alloc", "incref", "decref"))
        if op == "alloc":
            if alloc.num_free == 0:
                with pytest.raises(BlockPoolFullError):
                    alloc.alloc()
                continue
            bid = alloc.alloc()
            assert bid not in model and bid != 0
            model[bid] = 1
        elif op == "incref" and model:
            bid = rng.choice(list(model))
            alloc.incref(bid)
            model[bid] += 1
        elif op == "decref" and model:
            bid = rng.choice(list(model))
            freed = alloc.decref(bid)
            model[bid] -= 1
            assert freed == (model[bid] == 0)
            if model[bid] == 0:
                del model[bid]
        assert alloc.num_free + len(model) == num_blocks - 1
        for bid, n in model.items():
            assert alloc.refcount(bid) == n
    # double-free / foreign incref always rejected
    if model:
        bid = next(iter(model))
        for _ in range(model.pop(bid)):
            alloc.decref(bid)
        with pytest.raises(ValueError):
            alloc.decref(bid)
        with pytest.raises(ValueError):
            alloc.incref(bid)


@pytest.mark.parametrize("slot", [Slot("rec"), Slot("attn", cross_attn=True)],
                         ids=["recurrent", "cross"])
def test_paged_scheduler_says_which_slots_it_accepts(slot):
    from types import SimpleNamespace

    cfg = tiny_cfg(groups=(Group((Slot("attn"), slot), 1),))
    with pytest.raises(ValueError, match=r"accepts self-attention slots only "
                       r"\(kind='attn', full, windowed or latent"):
        PagedScheduler(SimpleNamespace(cfg=cfg), num_slots=2, num_blocks=8,
                       page=4, max_len=16)


def test_prefix_cache_eviction_releases_blocks():
    alloc = BlockAllocator(8)
    cache = PrefixCache()
    bids = [alloc.alloc() for _ in range(4)]
    for i, b in enumerate(bids):
        cache.insert_block(alloc, ("task", 0), 100 + i, b)
    cache.insert_full(alloc, ("task", 0), 13, 999, bids,
                      np.zeros((1, 1, 7), np.float32))
    for b in bids:  # the original owner retires
        alloc.decref(b)
    assert alloc.num_free == 3 - 0  # 7 allocatable - 4 cache-pinned
    cache.clear(alloc)
    assert alloc.num_free == 7
    assert not cache.blocks and not cache.full


# ---------------------------------------------------------------------------
# paged attention kernel vs dense oracle
# ---------------------------------------------------------------------------


def _pool_case(k=0, B=3, H=4, KH=2, D=16, page=8, nb=16, nbt=4):
    r = np.random.default_rng(k)
    q = jnp.asarray(r.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(r.standard_normal((nb, page, KH, D)), jnp.float32)
    vp = jnp.asarray(r.standard_normal((nb, page, KH, D)), jnp.float32)
    tables = jnp.asarray(
        r.choice(np.arange(1, nb), (B, nbt), replace=False), jnp.int32)
    lens = jnp.asarray(r.integers(1, nbt * page + 1, (B,)), jnp.int32)
    return q, kp, vp, tables, lens


@pytest.mark.parametrize("window", [None, 12, 8])
@pytest.mark.parametrize("cap", [0.0, 30.0])
def test_paged_attention_kernel_matches_ref(window, cap):
    q, kp, vp, tables, lens = _pool_case(0)
    want = ops.paged_attention(q, kp, vp, tables, lens, window=window,
                               cap=cap, impl="jnp")
    got = ops.paged_attention(q, kp, vp, tables, lens, window=window,
                              cap=cap, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("window", [None, 16])
def test_paged_attention_kernel_int8_matches_ref(window):
    q, kp, vp, tables, lens = _pool_case(1)
    qk = quantize(kp, "int8", axis=-1)
    qv = quantize(vp, "int8", axis=-1)
    want = ref.paged_attention_ref(q, qk.values, qv.values, tables, lens,
                                   window=window, k_scales=qk.scales,
                                   v_scales=qv.scales)
    got = ops.paged_attention(q, qk.values, qv.values, tables, lens,
                              window=window, k_scales=qk.scales,
                              v_scales=qv.scales, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_paged_attention_matches_contiguous_gather():
    """The paged oracle against plain dense attention over the manually
    gathered contiguous sequence - the exactness contract that makes
    paged fp32 decoding bit-identical to the slot scheduler."""
    q, kp, vp, tables, lens = _pool_case(2)
    B, H, D = q.shape
    KH = kp.shape[2]
    G = H // KH
    gk = np.asarray(kp)[np.asarray(tables)].reshape(B, -1, KH, D)
    gv = np.asarray(vp)[np.asarray(tables)].reshape(B, -1, KH, D)
    S = gk.shape[1]
    paged = np.asarray(ops.paged_attention(q, kp, vp, tables, lens,
                                           impl="jnp"))
    for b in range(B):
        L = int(lens[b])
        kb = jnp.repeat(jnp.asarray(gk[b:b + 1, :L]), G, axis=2)
        vb = jnp.repeat(jnp.asarray(gv[b:b + 1, :L]), G, axis=2)
        want = ref.attention_ref(
            q[b:b + 1, :, None], kb.transpose(0, 2, 1, 3),
            vb.transpose(0, 2, 1, 3), causal=False)
        np.testing.assert_allclose(paged[b], np.asarray(want)[0, :, 0],
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# paged decode path vs contiguous decode path (model level)
# ---------------------------------------------------------------------------


def _world(**kw):
    cfg = tiny_cfg(**kw)
    params = M.init_params(KEY, cfg)
    return cfg, params


@pytest.mark.parametrize("group", stacked_groups())
def test_paged_decode_logits_bit_exact(group):
    """fp32 paged decode == contiguous decode, logit-for-logit: the
    gathered view has the same length, chunking and masking as the
    contiguous cache. Over several layers and slots per group, a write
    or gather at the wrong layer or slot of the stacked caches shows."""
    cfg, params = _world(groups=(group,))
    max_len, page = 32, 8
    prompt = np.asarray(jax.random.randint(KEY, (1, 11), 1, 96))
    eng = ServeEngine(cfg, params)

    lc, caches = eng.prefill(prompt, max_len)
    pool = eng.init_paged_pool(num_blocks=10, page=page)
    # blocks 1 and 2 cover the 11-token prompt; deliberately NOT the
    # identity mapping to exercise the table indirection
    tables = np.zeros((1, max_len // page), np.int32)
    tables[0, :2] = [2, 1]
    _, fresh = eng.prefill(np.pad(prompt, ((0, 0), (0, 5))), 16,
                           last_pos=10)
    pool = eng.paged_insert(pool, fresh, tables[0, :2])
    tables[0, 2] = 3  # allocate-on-write target for positions 16..23

    tok = np.asarray([[7]], np.int32)
    for i in range(6):
        pos = np.asarray([11 + i], np.int32)
        lg_c, caches = eng.decode_step(caches, jnp.asarray(tok),
                                       jnp.asarray(pos))
        lg_p, pool = eng.paged_decode_step(pool, jnp.asarray(tok),
                                           jnp.asarray(pos), tables)
        np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))
        tok = np.asarray(jnp.argmax(lg_c[:, -1:], axis=-1), np.int32)


@pytest.mark.parametrize("step,kv_quant", [
    ("decode", None), ("decode", "int8"), ("verify", None), ("extend", None),
])
def test_paged_steps_update_the_pool_in_place(step, kv_quant):
    """The compiled paged decode, verify and extend steps update the
    donated pool in place, so they hold no pool-sized temp: the layer
    scan carries the stacked pool rather than rebuilding a second one as
    its output. float32: the CPU backend widens a bf16 pool to f32, a
    temp of its own that would hide the result."""
    cfg, params = _world(groups=(Group((Slot("attn"),), 4),))
    eng = ServeEngine(cfg, params)
    B, page, nbt = 4, 8, 4
    pool = eng.init_paged_pool(num_blocks=1024, page=page, kv_quant=kv_quant)
    tables = jnp.zeros((B, nbt), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    if step == "decode":
        lowered = eng._decode_paged.lower(
            eng.params, pool, jnp.zeros((B, 1), jnp.int32), pos, tables)
    elif step == "verify":
        lowered = eng._verify_paged.lower(
            eng.params, pool, jnp.zeros((B, 3), jnp.int32), pos, tables)
    else:
        S = 2 * page
        lowered = eng._extend.lower(
            eng.params, pool, jnp.zeros((1, S), jnp.int32), tables[:1],
            jnp.int32(0), jnp.int32(S), jnp.int32(S - 1))
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    pool_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(pool))
    assert temp < pool_bytes / 10, (temp, pool_bytes)


# ---------------------------------------------------------------------------
# scheduler-level: prefix sharing, COW isolation, int8, backpressure
# ---------------------------------------------------------------------------


def _reqs(rng, n, stem=None, new=5):
    out = []
    for i in range(n):
        if stem is not None and i % 2:
            prompt = np.concatenate(
                [stem, rng.integers(1, 96, int(rng.integers(1, 5)))])
        else:
            prompt = rng.integers(1, 96, int(rng.integers(3, 14)))
        out.append(Request(prompt=prompt.astype(np.int32),
                           max_new_tokens=new, eos_id=0))
    return out


def _contiguous_tokens(cfg, params, reqs, max_len=32):
    sched = make_scheduler(ServeEngine(cfg, params),
                           ServingConfig(num_slots=3, max_len=max_len))
    done, _ = sched.run([Request(prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 eos_id=r.eos_id) for r in reqs])
    return [c.tokens for c in done]


def test_warm_full_hit_skips_forward_and_stays_exact():
    cfg, params = _world()
    rng = np.random.default_rng(3)
    reqs = _reqs(rng, 6)
    want = _contiguous_tokens(cfg, params, reqs)

    eng = ServeEngine(cfg, params)
    sched = make_scheduler(eng, ServingConfig(
        num_slots=3, max_len=32, paged=True, page_size=8, num_blocks=48))
    done_cold, _ = sched.run(reqs)
    for w, c in zip(want, done_cold):
        np.testing.assert_array_equal(w, c.tokens)
    assert sched.stats["cold"] == 6 and sched.stats["full_hits"] == 0

    # identical prompts again: every admission is a full hit that replays
    # the cached last-token logits - zero prefill forward passes
    pf_calls = []
    orig = eng.prefill
    eng.prefill = lambda *a, **k: pf_calls.append(1) or orig(*a, **k)
    done_warm, _ = sched.run(
        [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                 eos_id=r.eos_id) for r in reqs])
    assert sched.stats["full_hits"] == 6 and not pf_calls
    for w, c in zip(want, done_warm):
        np.testing.assert_array_equal(w, c.tokens)


def test_partial_prefix_hit_extends_exactly():
    cfg, params = _world()
    rng = np.random.default_rng(4)
    stem = rng.integers(1, 96, 9)
    reqs = _reqs(rng, 8, stem=stem)
    want = _contiguous_tokens(cfg, params, reqs)

    sched = make_scheduler(ServeEngine(cfg, params), ServingConfig(
        num_slots=3, max_len=32, paged=True, page_size=8, num_blocks=64))
    done, _ = sched.run(reqs)
    assert sched.stats["partial_hits"] > 0
    for w, c in zip(want, done):
        np.testing.assert_array_equal(w, c.tokens)


def test_cow_fork_isolates_concurrent_sharers():
    """Three concurrent requests over ONE cached prompt whose tail block
    is partial: each must fork its own tail copy-on-write; a shared
    mutable tail would cross-corrupt their decode writes."""
    cfg, params = _world()
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 96, 11).astype(np.int32)  # 11 % 8 != 0
    mk = lambda: Request(prompt=prompt, max_new_tokens=5, eos_id=0)
    want = _contiguous_tokens(cfg, params, [mk()])[0]

    sched = make_scheduler(ServeEngine(cfg, params), ServingConfig(
        num_slots=3, max_len=32, paged=True, page_size=8, num_blocks=32))
    sched.run([mk()])  # seed the prefix cache
    done, _ = sched.run([mk(), mk(), mk()])  # admitted the same tick
    assert sched.stats["full_hits"] == 3
    for c in done:
        np.testing.assert_array_equal(want, c.tokens)


def test_int8_kv_blocks_bounded_top1():
    cfg, params = _world()
    rng = np.random.default_rng(6)
    reqs = _reqs(rng, 8, stem=rng.integers(1, 96, 9))
    want = np.concatenate(_contiguous_tokens(cfg, params, reqs))

    sched = make_scheduler(ServeEngine(cfg, params), ServingConfig(
        num_slots=3, max_len=32, paged=True, page_size=8, num_blocks=64,
        kv_quant="int8"))
    done, _ = sched.run(reqs)
    got = np.concatenate([c.tokens for c in done])
    n = min(len(got), len(want))
    assert (got[:n] == want[:n]).mean() >= 0.8


def test_block_exhaustion_backpressures_and_drains():
    """A pool far smaller than the offered load: admissions defer
    FIFO-fashion until retirements free blocks, every request still
    completes, and the pool ends empty (no leaked blocks/reservations)."""
    cfg, params = _world()
    rng = np.random.default_rng(7)
    reqs = _reqs(rng, 10)
    want = _contiguous_tokens(cfg, params, reqs)

    sched = make_scheduler(ServeEngine(cfg, params), ServingConfig(
        num_slots=4, max_len=32, paged=True, page_size=8, num_blocks=9,
        prefix_cache=False))
    done, _ = sched.run(reqs)
    assert [c.request_id for c in done] == list(range(10))
    for w, c in zip(want, done):
        np.testing.assert_array_equal(w, c.tokens)
    pr = sched.pool_report()
    assert pr["live_blocks"] == 0 and pr["reserved_blocks"] == 0


def test_oversized_request_rejected_at_submit():
    cfg, params = _world()
    sched = make_scheduler(ServeEngine(cfg, params), ServingConfig(
        num_slots=2, max_len=32, paged=True, page_size=8, num_blocks=3))
    with pytest.raises(ValueError):
        sched.submit(Request(prompt=np.arange(1, 20, dtype=np.int32),
                             max_new_tokens=8))


def test_windowed_config_runs_cold_and_validates_page():
    cfg = tiny_cfg(groups=(Group((Slot("attn", window=16),), 2),))
    params = M.init_params(KEY, cfg)
    rng = np.random.default_rng(8)
    reqs = _reqs(rng, 4)
    want = _contiguous_tokens(cfg, params, reqs)

    sched = make_scheduler(ServeEngine(cfg, params), ServingConfig(
        num_slots=2, max_len=32, paged=True, page_size=8, num_blocks=16))
    assert sched.prefix is None  # ring caches are not prefix-shareable
    done, _ = sched.run(reqs)
    for w, c in zip(want, done):
        np.testing.assert_array_equal(w, c.tokens)
    with pytest.raises(ValueError):  # ring 16 not a multiple of page 12
        make_scheduler(ServeEngine(cfg, params), ServingConfig(
            num_slots=2, max_len=24, paged=True, page_size=12,
            num_blocks=16))


# ---------------------------------------------------------------------------
# sharding: block pools replicate the allocator dims, shard kv heads
# ---------------------------------------------------------------------------


def test_paged_cache_spec_entries(monkeypatch):
    from repro.dist import sharding as sh

    monkeypatch.setattr(sh, "mesh_axis_sizes", lambda mesh: {"model": 2})
    cfg, _ = _world()
    spec = sh.paged_cache_spec("blocks/g0/slot0/attn/k/values",
                               (2, 16, 8, 2, 16), cfg, mesh=None)
    assert tuple(spec) == (None, None, None, "model", None)
    # MQA fallback: 1 kv head -> shard head_dim instead
    spec = sh.paged_cache_spec("blocks/g0/slot0/attn/v",
                               (2, 16, 8, 1, 16), cfg, mesh=None)
    assert tuple(spec) == (None, None, None, None, "model")
    # non-KV leaves (scales path strips to the same base) stay replicated
    spec = sh.paged_cache_spec("blocks/g0/slot0/attn/k/scales",
                               (2, 16, 8, 2, 1), cfg, mesh=None)
    assert tuple(spec) == (None, None, None, "model", None)
