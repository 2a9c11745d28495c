"""Continuous-batching scheduler behaviour: greedy parity with the static
engine (including admissions into freed slots mid-decode), slot reuse with
more requests than slots, heterogeneous task ids sharing one decode tick,
EOS retirement, and the sampling plumbing the scheduler relies on.
"""
import jax
import numpy as np
import pytest

from conftest import stacked_groups, tiny_cfg
from repro.common import tree as tu
from repro.common.types import AdapterCfg, Group, Slot
from repro.models import model as M
from repro.serving import ServingConfig, make_scheduler
from repro.serving.engine import MultiTaskEngine, ServeEngine
from repro.serving.scheduler import Request, Scheduler

KEY = jax.random.PRNGKey(0)


def _engine(**kw):
    cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard"), **kw)
    return ServeEngine(cfg, M.init_params(KEY, cfg)), cfg


@pytest.mark.parametrize("group", stacked_groups())
def test_scheduler_greedy_parity_with_static_engine(group):
    """Token-for-token equal to ServeEngine.generate for the same prompts -
    with num_slots < num_requests, so later requests are admitted into
    slots freed mid-decode and every step mixes requests at different
    positions."""
    eng, _ = _engine(groups=(group,))
    toks = np.asarray(jax.random.randint(KEY, (5, 8), 0, 97))
    want = eng.generate(toks, 6)

    sched = make_scheduler(eng, ServingConfig(num_slots=2, max_len=20))
    done, report = sched.run(
        [Request(prompt=toks[i], max_new_tokens=6) for i in range(5)])

    assert [c.request_id for c in done] == list(range(5))
    for i, c in enumerate(done):
        np.testing.assert_array_equal(c.tokens, want[i], err_msg=f"req{i}")
    assert report["requests"] == 5 and report["tokens"] == 30
    # 2 slots x 5 requests of 6 tokens each cannot finish in 6 lock-step
    # ticks: the run really was time-multiplexed over the slot pool
    assert report["ticks"] > 6


def test_scheduler_parity_with_local_window():
    """Per-slot ring-buffer decode (windowed attention) stays token-exact."""
    eng, _ = _engine(groups=(Group((Slot("attn", window=6),), 2),))
    toks = np.asarray(jax.random.randint(KEY, (3, 8), 0, 97))
    want = eng.generate(toks, 6)

    sched = make_scheduler(eng, ServingConfig(num_slots=2, max_len=20))
    done, _ = sched.run(
        [Request(prompt=toks[i], max_new_tokens=6) for i in range(3)])
    for i, c in enumerate(done):
        np.testing.assert_array_equal(c.tokens, want[i], err_msg=f"req{i}")


def test_slot_reuse_more_requests_than_slots():
    """Admit 7 requests into 2 slots with heterogeneous prompt lengths and
    budgets: all must complete with exactly their own budget."""
    eng, _ = _engine()
    rs = np.random.RandomState(3)
    reqs = [
        Request(prompt=rs.randint(0, 97, size=(3 + i % 4,)),
                max_new_tokens=1 + i % 5)
        for i in range(7)
    ]
    sched = make_scheduler(eng, ServingConfig(num_slots=2, max_len=16))
    done, report = sched.run(reqs)

    assert len(done) == 7
    for i, c in enumerate(done):
        assert len(c.tokens) == reqs[i].max_new_tokens, i
        assert c.prompt_len == len(reqs[i].prompt)
        assert c.finish_reason == "length"
        assert c.ttft_s >= 0 and c.latency_s >= c.ttft_s
    assert report["requests"] == 7
    assert report["tokens"] == sum(r.max_new_tokens for r in reqs)


def test_mixed_task_tick():
    """Requests with different task ids share every decode tick; each must
    get its own adapter (parity with a dedicated single-task engine)."""
    cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard"))
    p0 = M.init_params(KEY, cfg)
    p1 = tu.map_with_path(
        lambda path, v: v + 0.5 if "adapter/b" in path else v, p0)
    toks = np.asarray(jax.random.randint(KEY, (4, 8), 0, 97))
    want0 = ServeEngine(cfg, p0).generate(toks, 5)
    want1 = ServeEngine(cfg, p1).generate(toks, 5)

    eng = MultiTaskEngine(cfg, [p0, p1])
    sched = make_scheduler(eng, ServingConfig(num_slots=3, max_len=16))
    done, _ = sched.run(
        [Request(prompt=toks[i], max_new_tokens=5, task_id=i % 2)
         for i in range(4)])
    for i, c in enumerate(done):
        want = (want0 if i % 2 == 0 else want1)[i]
        np.testing.assert_array_equal(c.tokens, want, err_msg=f"req{i}")


def test_eos_retires_slot_early():
    eng, _ = _engine()
    toks = np.asarray(jax.random.randint(KEY, (1, 8), 0, 97))
    want = eng.generate(toks, 6)[0]
    eos = int(want[2])

    sched = make_scheduler(eng, ServingConfig(num_slots=1, max_len=20))
    done, _ = sched.run(
        [Request(prompt=toks[0], max_new_tokens=6, eos_id=eos)])
    assert done[0].finish_reason == "eos"
    np.testing.assert_array_equal(done[0].tokens, want[:3])


def test_submit_rejects_over_budget_prompt():
    eng, _ = _engine()
    sched = make_scheduler(eng, ServingConfig(num_slots=1, max_len=8))
    with pytest.raises(ValueError, match="exceeds slot cache length"):
        sched.submit(Request(prompt=np.zeros(6, np.int32), max_new_tokens=4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit(Request(prompt=np.zeros(2, np.int32), max_new_tokens=0))


def test_prefill_bucketing_token_exact():
    """Right-padded bucketed prefill must not change a single token, for
    prompts both below and exactly at the bucket boundary."""
    eng, _ = _engine()
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 97, size=(n,)) for n in (3, 5, 8, 11)]
    want = [eng.generate(p.reshape(1, -1), 5)[0] for p in prompts]

    sched = make_scheduler(eng, ServingConfig(num_slots=2, max_len=20,
                                              prefill_bucket=8))
    done, _ = sched.run(
        [Request(prompt=p, max_new_tokens=5) for p in prompts])
    for i, c in enumerate(done):
        np.testing.assert_array_equal(c.tokens, want[i], err_msg=f"req{i}")


def test_prefill_bucketing_rejects_windowed_configs():
    eng, _ = _engine(groups=(Group((Slot("attn", window=6),), 2),))
    with pytest.raises(ValueError, match="full-attention"):
        make_scheduler(eng, ServingConfig(num_slots=1, max_len=16,
                                          prefill_bucket=8))


def test_scheduler_topk_sampling_deterministic_per_seed():
    """Per-request rng: same seed -> same continuation, independent of
    which slot the request lands in or what else shares the batch."""
    eng, _ = _engine()
    toks = np.asarray(jax.random.randint(KEY, (3, 8), 0, 97))

    def sample(order):
        sched = make_scheduler(eng,
                               ServingConfig(num_slots=2, max_len=20))
        done, _ = sched.run(
            [Request(prompt=toks[i], max_new_tokens=5, top_k=40, seed=7 + i)
             for i in order])
        return {tuple(toks[order[j]]): tuple(c.tokens)
                for j, c in enumerate(done)}

    a = sample([0, 1, 2])
    b = sample([2, 1, 0])  # different slot assignment + batch mix
    assert a == b


# ---------------------------------------------------------------------------
# seeded fuzz: randomized traffic vs the static-engine oracle
# ---------------------------------------------------------------------------

_FUZZ_WORLD = {}


def _fuzz_world():
    """Shared backbone + 4 named adapters + static oracle + hot engine
    (2-row bank), built once: fuzz episodes reuse the compiled ticks."""
    if not _FUZZ_WORLD:
        import tempfile

        from repro.core.hadamard import extract_delta, perturb_adapters
        from repro.serving.registry import AdapterBank, AdapterRegistry

        cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard"))
        base = M.init_params(KEY, cfg)
        variants = [
            perturb_adapters(base, jax.random.fold_in(KEY, 50 + t), scale=0.2)
            for t in range(4)
        ]
        td = tempfile.mkdtemp()
        registry = AdapterRegistry(td)
        for t, v in enumerate(variants):
            registry.publish(f"task{t}", extract_delta(v))
        _FUZZ_WORLD.update(
            cfg=cfg,
            oracle=MultiTaskEngine(cfg, variants),
            hot=MultiTaskEngine(cfg, AdapterBank(cfg, base, 2, registry)),
        )
    return _FUZZ_WORLD


def _oracle_tokens(oracle, prompt, task, budget, eos):
    """Reference continuation: lock-step B=1 generation truncated at the
    first EOS (inclusive), exactly the scheduler's retirement rule."""
    out = np.asarray(oracle.generate_for_tasks(
        prompt.reshape(1, -1), np.array([task]), budget))[0]
    if eos is not None:
        hit = np.flatnonzero(out == eos)
        if hit.size:
            out = out[: hit[0] + 1]
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_scheduler_fuzz_against_static_oracle(seed):
    """Seeded random traffic - staggered arrival ticks, random prompt
    lengths, budgets, adapter names (through a 2-row hot-swap bank, so
    admissions race evictions), and EOS patterns engineered to fire
    mid-stream on ~a third of requests - must be token-exact against the
    lock-step static oracle, request by request."""
    w = _fuzz_world()
    rs = np.random.RandomState(100 + seed)
    n_req = 14
    max_len = 16

    reqs, wants = [], []
    for i in range(n_req):
        plen = int(rs.randint(2, 9))
        budget = int(rs.randint(1, 7))
        task = int(rs.randint(0, 4))
        prompt = rs.randint(0, 97, size=(plen,)).astype(np.int32)
        ref_full = _oracle_tokens(w["oracle"], prompt, task, budget, None)
        mode = rs.randint(0, 3)
        if mode == 0 and budget > 1:
            eos = int(ref_full[rs.randint(0, budget)])  # fires mid-stream
        elif mode == 1:
            eos = 96  # may or may not appear - oracle truncates identically
        else:
            eos = None
        arrival = int(rs.randint(0, 10))
        reqs.append((arrival, Request(
            prompt=prompt, max_new_tokens=budget, adapter=f"task{task}",
            eos_id=eos)))
        wants.append(_oracle_tokens(w["oracle"], prompt, task, budget, eos))

    sched = make_scheduler(w["hot"],
                           ServingConfig(num_slots=3, max_len=max_len))
    ids = [None] * n_req
    t = 0
    while None in ids or sched.pending or sched.active:
        for i, (arr, r) in enumerate(reqs):
            if ids[i] is None and arr <= t:
                ids[i] = sched.submit(r)
        sched.step()
        t += 1
        assert t < 500, "fuzz episode failed to drain"

    for i, rid in enumerate(ids):
        c = sched.completions.pop(rid)
        np.testing.assert_array_equal(
            c.tokens, wants[i],
            err_msg=f"seed {seed} req {i} ({reqs[i][1].adapter}, "
                    f"eos={reqs[i][1].eos_id})")
        want_reason = ("eos" if reqs[i][1].eos_id is not None
                       and wants[i].size
                       and wants[i][-1] == reqs[i][1].eos_id
                       else "length")
        assert c.finish_reason == want_reason, f"seed {seed} req {i}"

    # lifecycle hygiene after every episode: no leaked pins, no retraces
    bank = w["hot"].adapter_bank
    for name in list(bank.resident):
        assert bank.pins(name) == 0, name
    assert w["hot"].trace_counts["decode"] == 1, w["hot"].trace_counts


def test_generate_for_tasks_plumbs_sampling():
    """Regression: MultiTaskEngine.generate_for_tasks used to drop
    rng/top_k (multi-task serving was greedy-only)."""
    cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard"))
    p0 = M.init_params(KEY, cfg)
    p1 = tu.map_with_path(
        lambda path, v: v + 0.5 if "adapter/b" in path else v, p0)
    eng = MultiTaskEngine(cfg, [p0, p1])
    toks = np.asarray(jax.random.randint(KEY, (2, 8), 0, 97))
    tids = np.array([0, 1])

    firsts = {
        tuple(np.asarray(eng.generate_for_tasks(
            toks, tids, 2, rng=jax.random.PRNGKey(s), top_k=40)).ravel())
        for s in range(8)
    }
    assert len(firsts) > 1  # greedy-only would collapse to one outcome

    a = eng.generate_for_tasks(toks, tids, 4, rng=jax.random.PRNGKey(5),
                               top_k=40)
    b = eng.generate_for_tasks(toks, tids, 4, rng=jax.random.PRNGKey(5),
                               top_k=40)
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# paged fuzz: overlapping-prefix traffic vs the static oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_paged_scheduler_fuzz_against_static_oracle(seed):
    """Randomized traffic through the PAGED scheduler - >=50% of requests
    share prompt stems (exercising partial/full prefix hits and COW tail
    forks), arrivals land mid-decode, and the pool is deliberately small
    enough that admissions hit block-exhaustion backpressure and prefix-
    cache eviction - must be token-exact against the lock-step static
    oracle at fp32, with the paged decode tick traced exactly once."""
    w = _fuzz_world()
    rs = np.random.RandomState(300 + seed)
    n_req = 14
    max_len, page = 16, 4

    stems = [rs.randint(0, 97, size=(int(rs.randint(4, 8)),)) for _ in range(3)]
    reqs, wants = [], []
    for i in range(n_req):
        if i % 2 or i % 5 == 0:  # ~60%: shared stem + random tail
            stem = stems[rs.randint(0, len(stems))]
            prompt = np.concatenate(
                [stem, rs.randint(0, 97, size=(int(rs.randint(0, 3)),))])
        else:
            prompt = rs.randint(0, 97, size=(int(rs.randint(2, 9)),))
        prompt = prompt.astype(np.int32)
        budget = int(rs.randint(1, 7))
        task = int(rs.randint(0, 4))
        ref_full = _oracle_tokens(w["oracle"], prompt, task, budget, None)
        mode = rs.randint(0, 3)
        if mode == 0 and budget > 1:
            eos = int(ref_full[rs.randint(0, budget)])
        elif mode == 1:
            eos = 96
        else:
            eos = None
        arrival = int(rs.randint(0, 10))
        reqs.append((arrival, Request(
            prompt=prompt, max_new_tokens=budget, task_id=task, eos_id=eos)))
        wants.append(_oracle_tokens(w["oracle"], prompt, task, budget, eos))

    # 12 allocatable blocks for 3 slots x up to 4-block requests plus the
    # prefix cache: admission regularly has to evict and/or defer
    sched = make_scheduler(w["oracle"], ServingConfig(
        num_slots=3, max_len=max_len, paged=True, page_size=page,
        num_blocks=13))
    ids = [None] * n_req
    t = 0
    while None in ids or sched.pending or sched.active:
        for i, (arr, r) in enumerate(reqs):
            if ids[i] is None and arr <= t:
                ids[i] = sched.submit(r)
        sched.step()
        t += 1
        assert t < 500, "paged fuzz episode failed to drain"

    for i, rid in enumerate(ids):
        c = sched.completions.pop(rid)
        np.testing.assert_array_equal(
            c.tokens, wants[i],
            err_msg=f"seed {seed} req {i} (task{reqs[i][1].task_id}, "
                    f"eos={reqs[i][1].eos_id})")
        want_reason = ("eos" if reqs[i][1].eos_id is not None
                       and wants[i].size
                       and wants[i][-1] == reqs[i][1].eos_id
                       else "length")
        assert c.finish_reason == want_reason, f"seed {seed} req {i}"

    # pool hygiene: only prefix-cache pins survive the episode, clearing
    # them leaves every block free with nothing reserved
    pr = sched.pool_report()
    assert pr["reserved_blocks"] == 0
    pinned = (set(sched.prefix.blocks.values())
              | {b for bids, _ in sched.prefix.full.values() for b in bids})
    assert pr["live_blocks"] == len(pinned)
    sched.prefix.clear(sched.alloc)
    assert sched.pool_report()["live_blocks"] == 0
    assert w["oracle"].trace_counts["decode_paged"] == 1, \
        w["oracle"].trace_counts


def test_paged_scheduler_fuzz_windowed_cold_lane():
    """Windowed config through the paged scheduler: ring layouts disable
    prefix sharing (cold lane), but paging + backpressure must still be
    token-exact vs the contiguous scheduler under staggered traffic."""
    eng, cfg = _engine(groups=(Group((Slot("attn", window=8),), 2),))
    rs = np.random.RandomState(7)
    reqs = [Request(prompt=rs.randint(0, 97, size=(int(rs.randint(2, 12)),))
                    .astype(np.int32),
                    max_new_tokens=int(rs.randint(1, 6)), eos_id=96)
            for _ in range(8)]

    want, _ = make_scheduler(eng, ServingConfig(num_slots=3,
                                                max_len=16)).run(
        [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                 eos_id=r.eos_id) for r in reqs])
    sched = make_scheduler(eng, ServingConfig(
        num_slots=3, max_len=16, paged=True, page_size=4, num_blocks=7))
    assert sched.prefix is None
    done, _ = sched.run(reqs)
    for wc, c in zip(want, done):
        np.testing.assert_array_equal(wc.tokens, c.tokens)
        assert wc.finish_reason == c.finish_reason
    assert sched.pool_report()["live_blocks"] == 0


# ---------------------------------------------------------------------------
# sampling temperature validation (temperature=0 means greedy, not 1e6x)
# ---------------------------------------------------------------------------


def test_temperature_zero_decodes_greedily():
    """temperature=0 with top_k set must reproduce the greedy continuation
    exactly (it used to be clamped to 1e-6, turning the logits into a 1e6x
    blow-up instead of the argmax the caller asked for)."""
    from repro.serving.engine import sample_greedy, sample_topk

    eng, _ = _engine()
    toks = np.asarray(jax.random.randint(KEY, (3, 8), 0, 97))
    want = eng.generate(toks, 5)  # greedy oracle

    sched = make_scheduler(eng, ServingConfig(num_slots=2, max_len=20))
    done, _ = sched.run(
        [Request(prompt=toks[i], max_new_tokens=5, top_k=40,
                 temperature=0.0, seed=7 + i) for i in range(3)])
    for i, c in enumerate(done):
        np.testing.assert_array_equal(c.tokens, want[i], err_msg=f"req{i}")

    logits = jax.random.normal(KEY, (2, 4, 97))
    np.testing.assert_array_equal(
        np.asarray(sample_topk(logits, KEY, 40, temperature=0.0)),
        np.asarray(sample_greedy(logits)))


def test_submit_rejects_invalid_temperature():
    eng, _ = _engine()
    sched = make_scheduler(eng, ServingConfig(num_slots=1, max_len=16))
    for bad in (-1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="temperature"):
            sched.submit(Request(prompt=np.zeros(2, np.int32),
                                 max_new_tokens=2, temperature=bad))
