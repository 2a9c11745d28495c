"""Speculative decoding (serving/spec.py) and the unified serving API
(serving/config.py + ServeEngine.generate + deprecation shims).

Covers the PR's acceptance contract:
  * greedy speculative decoding is token-for-token identical to plain
    greedy decoding, over both the contiguous and paged targets, with
    mixed greedy/sampled tenants and adapter rows mixed per tick, and the
    draft/verify jits each traced exactly once (zero-retrace invariant)
  * the rejection path really runs (perturbed adapters: some drafts
    accepted, some rejected) and still never changes a token - KV
    rollback-by-overwrite is invisible
  * construction-time validation: windowed targets, non-Hadamard
    self-drafts, overflowing submits, incoherent ServingConfigs
  * make_scheduler picks the right scheduler class per config and
    enforces engine/draft-model coherence
  * generate(list[Request]) subsumes the legacy generate_for_tasks /
    generate_for_adapters entry points: the shims warn DeprecationWarning
    and return token-identical output
"""
import tempfile
import warnings

import jax
import numpy as np
import pytest

from conftest import stacked_groups, tiny_cfg
from repro.common.types import AdapterCfg, Group, Slot
from repro.core.hadamard import extract_delta, perturb_adapters
from repro.models import model as M
from repro.serving import (AdapterBank, AdapterRegistry, DraftLane,
                           MultiTaskEngine, PagedScheduler, Request,
                           Scheduler, ServeEngine, ServingConfig,
                           SpecPagedScheduler, SpecScheduler, make_scheduler)

KEY = jax.random.PRNGKey(11)


def _world(**kw):
    cfg = tiny_cfg(**kw)
    base = M.init_params(KEY, cfg)
    # near-identity task rows: most self-drafts land, some are rejected,
    # so identity checks exercise accept AND reject (untied head - a tied
    # random head echoes its input token and never rejects anything)
    tasks = [perturb_adapters(base, jax.random.fold_in(KEY, 40 + t),
                              scale=0.01) for t in range(3)]
    return {"cfg": cfg, "base": base, "tasks": tasks}


@pytest.fixture(scope="module")
def world():
    return _world()


def _mixed_reqs(n=6, budget=5):
    rs = np.random.RandomState(17)
    reqs = []
    for i in range(n):
        kw = {"top_k": 5, "seed": 3} if i == n - 1 else {}  # one sampled
        reqs.append(Request(prompt=rs.randint(0, 97, size=(6,))
                            .astype(np.int32),
                            max_new_tokens=budget, task_id=i % 3, **kw))
    return reqs


def _assert_same_tokens(done_a, done_b):
    for ca, cb in zip(done_a, done_b):
        np.testing.assert_array_equal(ca.tokens, cb.tokens,
                                      err_msg=f"req{ca.request_id}")


# ---------------------------------------------------------------------------
# token identity: contiguous and paged, mixed tenants, zero retrace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("group", stacked_groups())
def test_spec_token_identity_contiguous_mixed_tenants(group):
    """Speculative greedy == plain greedy over the contiguous slot pool,
    with 3 adapter rows and one sampled (top_k) tenant sharing every
    tick; verify and draft each compile exactly once."""
    world = _world(groups=(group,))
    eng = MultiTaskEngine(world["cfg"], world["tasks"])
    plain = make_scheduler(eng, ServingConfig(num_slots=3, max_len=32))
    spec = make_scheduler(eng, ServingConfig(num_slots=3, max_len=32,
                                             spec_k=3))
    assert isinstance(spec, SpecScheduler)

    done_p, _ = plain.run(_mixed_reqs())
    done_s, _ = spec.run(_mixed_reqs())
    _assert_same_tokens(done_p, done_s)

    st = spec.spec_stats
    assert st["drafted"] > 0 and st["spec_ticks"] > 0
    assert eng.trace_counts["verify"] == 1, eng.trace_counts
    assert spec.draft_lane.trace_counts["draft"] == 1, \
        spec.draft_lane.trace_counts


@pytest.mark.parametrize("group", stacked_groups())
def test_spec_token_identity_paged_with_rejections(group):
    """Speculative greedy == plain greedy over the paged block pool, with
    the rejection path demonstrably exercised: rejected verify positions
    were written into real KV blocks and then overwritten, and no token
    moved."""
    world = _world(groups=(group,))
    eng = MultiTaskEngine(world["cfg"], world["tasks"])
    serve = dict(num_slots=3, max_len=32, paged=True, page_size=8)
    plain = make_scheduler(eng, ServingConfig(**serve))
    spec = make_scheduler(eng, ServingConfig(**serve, spec_k=3))
    assert isinstance(spec, SpecPagedScheduler)

    done_p, _ = plain.run(_mixed_reqs())
    done_s, _ = spec.run(_mixed_reqs())
    _assert_same_tokens(done_p, done_s)

    st = spec.spec_stats
    assert st["accepted"] < st["drafted"], (
        f"perturbed adapters must reject some drafts: {st}")
    assert eng.trace_counts["verify_paged"] == 1, eng.trace_counts
    # pool hygiene: widened allocate-on-write leaked nothing
    spec.prefix.clear(spec.alloc)
    assert spec.pool_report()["live_blocks"] == 0


def test_spec_all_accept_needs_fewer_ticks(world):
    """Identity adapters (= the frozen backbone): every draft matches, so
    a k-spec run must finish in far fewer ticks than plain decode while
    staying token-identical."""
    cfg, base = world["cfg"], world["base"]
    eng = MultiTaskEngine(cfg, [base, base])
    plain = make_scheduler(eng, ServingConfig(num_slots=2, max_len=32))
    spec = make_scheduler(eng, ServingConfig(num_slots=2, max_len=32,
                                             spec_k=4))

    rs = np.random.RandomState(23)
    mk = lambda: [Request(prompt=rs.randint(0, 97, size=(5,))
                          .astype(np.int32), max_new_tokens=10,
                          task_id=i % 2) for i in range(2)]
    rs = np.random.RandomState(23)
    done_p, rep_p = plain.run(mk())
    rs = np.random.RandomState(23)
    done_s, rep_s = spec.run(mk())
    _assert_same_tokens(done_p, done_s)
    assert spec.acceptance_rate == 1.0, spec.spec_stats
    # 10-token budget at k=4: 2 verify ticks (+1 admission tick margin)
    assert rep_s["ticks"] <= 3 < rep_p["ticks"], (rep_s, rep_p)


def test_spec_separate_draft_model(world):
    """spec_draft='model': an unrelated same-vocab draft model drafts -
    acceptance is poor but tokens are still exactly the target's."""
    cfg, base = world["cfg"], world["base"]
    eng = MultiTaskEngine(cfg, world["tasks"])
    dparams = M.init_params(jax.random.fold_in(KEY, 99), cfg)
    plain = make_scheduler(eng, ServingConfig(num_slots=2, max_len=32))
    spec = make_scheduler(
        eng, ServingConfig(num_slots=2, max_len=32, spec_k=2,
                           spec_draft="model"),
        draft_model=(cfg, dparams))

    reqs = _mixed_reqs(n=4, budget=4)
    done_p, _ = plain.run(_mixed_reqs(n=4, budget=4))
    done_s, _ = spec.run(reqs)
    _assert_same_tokens(done_p, done_s)
    assert spec.spec_stats["drafted"] > 0


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------


def test_spec_submit_overflow_rejected(world):
    eng = MultiTaskEngine(world["cfg"], world["tasks"])
    spec = make_scheduler(eng, ServingConfig(num_slots=2, max_len=16,
                                             spec_k=4))
    with pytest.raises(ValueError, match="spec_k"):
        spec.submit(Request(prompt=np.zeros(8, np.int32), max_new_tokens=5))
    # the same request fits a plain scheduler (8 + 5 <= 16)
    plain = make_scheduler(eng, ServingConfig(num_slots=2, max_len=16))
    plain.submit(Request(prompt=np.zeros(8, np.int32), max_new_tokens=5))


def test_spec_windowed_target_rejected():
    cfg = tiny_cfg(groups=(Group((Slot("attn", window=8),), 2),))
    eng = ServeEngine(cfg, M.init_params(KEY, cfg))
    with pytest.raises(ValueError, match="full-attention"):
        make_scheduler(eng, ServingConfig(num_slots=2, max_len=32,
                                          spec_k=2))


def test_self_spec_requires_hadamard_adapter():
    class _Eng:  # DraftLane rejects before touching anything but cfg
        cfg = tiny_cfg(adapter=AdapterCfg(kind="lora"))

    with pytest.raises(ValueError, match="hadamard"):
        DraftLane(_Eng(), num_slots=2, max_len=32, k=2)


def test_draft_model_vocab_must_match(world):
    eng = MultiTaskEngine(world["cfg"], world["tasks"])
    dcfg = tiny_cfg(vocab_size=89)
    dparams = M.init_params(KEY, dcfg)
    with pytest.raises(ValueError, match="vocab"):
        make_scheduler(
            eng, ServingConfig(num_slots=2, max_len=32, spec_k=2,
                               spec_draft="model"),
            draft_model=(dcfg, dparams))


@pytest.mark.parametrize("kw", [
    dict(num_slots=0),
    dict(max_len=0),
    dict(kv_quant="int8"),                       # quantized KV needs paging
    dict(kv_quant="int4", paged=True),           # unknown mode
    dict(num_blocks=8),                          # pool size needs paging
    dict(paged=True, page_size=16, max_len=40),  # not page-aligned
    dict(paged=True, page_size=16, num_blocks=1),  # null block only
    dict(paged=True, page_size=16, max_len=32, prefill_bucket=12),
    dict(spec_k=-1),
    dict(spec_draft="oracle", spec_k=2),
    dict(spec_draft="model"),                    # meaningless at spec_k=0
    dict(prefill_bucket=0),
    dict(top_k=-1),
])
def test_serving_config_rejects_incoherent_combos(kw):
    with pytest.raises(ValueError):
        ServingConfig(**kw)


def test_make_scheduler_selection_and_coherence(world):
    cfg, tasks = world["cfg"], world["tasks"]
    eng = MultiTaskEngine(cfg, tasks)
    assert type(make_scheduler(
        eng, ServingConfig(num_slots=2, max_len=32))) is Scheduler
    assert type(make_scheduler(
        eng, ServingConfig(num_slots=2, max_len=32, paged=True,
                           page_size=8))) is PagedScheduler
    assert type(make_scheduler(
        eng, ServingConfig(num_slots=2, max_len=32,
                           spec_k=2))) is SpecScheduler
    assert type(make_scheduler(
        eng, ServingConfig(num_slots=2, max_len=32, paged=True, page_size=8,
                           spec_k=2))) is SpecPagedScheduler

    # auto pool sizing: 1.5x worst-case cover + the null block
    sched = make_scheduler(eng, ServingConfig(num_slots=2, max_len=32,
                                              paged=True, page_size=8))
    assert sched.alloc.num_blocks == 1 + 2 * (32 // 8) * 3 // 2

    # engine/backbone-quant coherence
    with pytest.raises(ValueError, match="backbone_quant"):
        make_scheduler(eng, ServingConfig(num_slots=2, max_len=32,
                                          backbone_quant="int8"))
    qeng = MultiTaskEngine(cfg, tasks, quant="int8")
    make_scheduler(qeng, ServingConfig(num_slots=2, max_len=32,
                                       backbone_quant="int8"))

    # draft_model coherence
    with pytest.raises(ValueError, match="draft_model"):
        make_scheduler(eng, ServingConfig(num_slots=2, max_len=32, spec_k=2,
                                          spec_draft="model"))
    with pytest.raises(ValueError, match="spec_draft"):
        make_scheduler(eng, ServingConfig(num_slots=2, max_len=32, spec_k=2),
                       draft_model=(cfg, world["base"]))
    with pytest.raises(ValueError, match="spec_k"):
        make_scheduler(eng, ServingConfig(num_slots=2, max_len=32),
                       draft_model=(cfg, world["base"]))


# ---------------------------------------------------------------------------
# unified generate + deprecation shims
# ---------------------------------------------------------------------------


def test_generate_request_list_matches_array_path(world):
    cfg, base = world["cfg"], world["base"]
    eng = ServeEngine(cfg, base)
    toks = np.asarray(jax.random.randint(KEY, (3, 6), 0, 97))
    want = eng.generate(toks, 5)

    out = eng.generate([Request(prompt=toks[i], max_new_tokens=5)
                        for i in range(3)])
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i])

    # per-request budgets truncate rows individually
    out = eng.generate([Request(prompt=toks[i], max_new_tokens=2 + i)
                        for i in range(3)])
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i, :2 + i])

    # eos_id truncates inclusively
    eos = int(want[0, 2])
    out = eng.generate([Request(prompt=toks[0], max_new_tokens=5,
                                eos_id=eos)])
    cut = np.flatnonzero(want[0] == eos)[0] + 1
    np.testing.assert_array_equal(out[0], want[0, :cut])


def test_generate_request_list_validation(world):
    cfg, base = world["cfg"], world["base"]
    eng = ServeEngine(cfg, base)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.generate(np.zeros((1, 4), np.int32))
    with pytest.raises(ValueError, match="same-length"):
        eng.generate([Request(prompt=np.zeros(4, np.int32),
                              max_new_tokens=2),
                      Request(prompt=np.zeros(6, np.int32),
                              max_new_tokens=2)])
    with pytest.raises(ValueError, match="MultiTaskEngine"):
        eng.generate([Request(prompt=np.zeros(4, np.int32),
                              max_new_tokens=2, task_id=1)])
    assert eng.generate([]) == []


def test_generate_for_tasks_shim_warns_and_matches(world):
    cfg, tasks = world["cfg"], world["tasks"]
    eng = MultiTaskEngine(cfg, tasks)
    toks = np.asarray(jax.random.randint(KEY, (3, 6), 0, 97))
    tids = np.array([2, 0, 1])

    with pytest.warns(DeprecationWarning, match="generate_for_tasks"):
        old = eng.generate_for_tasks(toks, tids, 4)
    new = eng.generate([Request(prompt=toks[i], max_new_tokens=4,
                                task_id=int(tids[i])) for i in range(3)])
    np.testing.assert_array_equal(old, np.stack(new))

    # sampled: the call-level rng reproduces the legacy stream exactly
    with pytest.warns(DeprecationWarning):
        old = eng.generate_for_tasks(toks, tids, 4,
                                     rng=jax.random.PRNGKey(5), top_k=7)
    new = eng.generate([Request(prompt=toks[i], max_new_tokens=4,
                                task_id=int(tids[i])) for i in range(3)],
                       rng=jax.random.PRNGKey(5), top_k=7)
    np.testing.assert_array_equal(old, np.stack(new))


def test_generate_for_adapters_shim_warns_and_matches(world):
    cfg, base, tasks = world["cfg"], world["base"], world["tasks"]
    toks = np.asarray(jax.random.randint(KEY, (3, 6), 0, 97))
    names = ["task0", "task1", "task0"]
    with tempfile.TemporaryDirectory() as td:
        reg = AdapterRegistry(td)
        for t in range(2):
            reg.publish(f"task{t}", extract_delta(tasks[t]))
        hot = MultiTaskEngine(cfg, AdapterBank(cfg, base, 2, reg))

        with pytest.warns(DeprecationWarning, match="generate_for_adapters"):
            old = hot.generate_for_adapters(toks, names, 4)
        new = hot.generate([Request(prompt=toks[i], max_new_tokens=4,
                                    adapter=names[i]) for i in range(3)])
        np.testing.assert_array_equal(old, np.stack(new))
        for n in set(names):  # pins released
            assert hot.adapter_bank.pins(n) == 0

    # the static oracle agrees row-for-row
    static = MultiTaskEngine(cfg, tasks)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        want = static.generate_for_tasks(toks, np.array([0, 1, 0]), 4)
    np.testing.assert_array_equal(old, want)
