"""The traffic generator: deterministic per seed, the same work for every
seed, and the distributions the traffic file states."""
import json
import pathlib
import sys

import numpy as np

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.append(str(REPO))  # the harness is the package `bench` there

from bench import traffic  # noqa: E402
CHAT = json.loads((REPO / "bench/traffic/chat.json").read_text())
SEED = 2**31 + 12345


def plan(seed, segments=(20.0, 51.0, 60.0)):
    return traffic.open_loop(CHAT, seed, segments, vocab=151936)


def test_same_seed_same_requests():
    a, b = plan(SEED), plan(SEED)
    assert [(p.due_s, p.max_new_tokens, p.tenant) for p in a] == \
        [(p.due_s, p.max_new_tokens, p.tenant) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))


def test_seeds_share_the_work_in_another_order():
    a, b = plan(SEED), plan(7)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new_tokens for p in a) == \
        sorted(p.max_new_tokens for p in b)
    assert sorted(p.tenant for p in a) == sorted(p.tenant for p in b)
    # the window, 20 s to 71 s, holds the same work for both
    def window(ps):
        inside = [p for p in ps if 20.0 <= p.due_s < 71.0]
        return (sorted(len(p.prompt) for p in inside),
                sorted(p.max_new_tokens for p in inside))
    assert window(a) == window(b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]


def test_stated_distributions():
    ps = plan(SEED, segments=(400.0,))
    n = len(ps)
    assert n == int(np.ceil(CHAT["arrivals"]["rate_rps"] * 400.0))
    S = np.array([len(p.prompt) for p in ps])
    out = np.array([p.max_new_tokens for p in ps])
    spec_p, spec_o = CHAT["prompt_len"], CHAT["output_len"]
    assert S.min() >= spec_p["min"] and S.max() <= spec_p["max"]
    assert out.min() >= spec_o["min"] and out.max() <= spec_o["max"]
    assert abs(np.median(S) - spec_p["median"]) <= 2
    assert abs(np.median(out) - spec_o["median"]) <= 2
    # the lognormal's sigma: the quartiles sit at median x exp(+-0.674 sigma)
    q1, q3 = np.percentile(S, [25, 75])
    assert abs(np.log(q3 / q1) / (2 * 0.6745) - spec_p["sigma"]) < 0.05
    # Poisson arrivals: mean gap 1/rate, coefficient of variation near 1
    gaps = np.diff([0.0] + [p.due_s for p in ps])
    assert abs(gaps.mean() * CHAT["arrivals"]["rate_rps"] - 1) < 0.02
    assert abs(gaps.std() / gaps.mean() - 1) < 0.1
    # Zipf s=1 over 64 tenants: row 0 takes 1/H_64 of the requests
    share = np.mean([p.tenant == 0 for p in ps])
    h64 = sum(1 / k for k in range(1, 65))
    assert abs(share - 1 / h64) < 0.01
    assert max(p.tenant for p in ps) < CHAT["tenants"]["n"]
    toks = np.concatenate([p.prompt for p in ps])
    assert toks.min() >= traffic.FIRST_TOKEN_ID and toks.max() < 151936
