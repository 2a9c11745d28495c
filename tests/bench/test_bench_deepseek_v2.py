"""The DeepSeek-V2 architecture module (`bench/arch/deepseek_v2.py`): its
layout against the program's tree, its counts worked by hand at the
published widths, its reference's gaps on the reference's own tokens, and
the new cell run end to end through `bench/run.py`'s lookup at CPU size."""
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.append(str(REPO))  # the harness is the package `bench` there

from bench import common, run, weights  # noqa: E402

CELL = "deepseek-v2-lite-ep8.chat-decode"
CONF = REPO / "bench/configs/deepseek-v2-lite-ep8.json"
SEED = 2**31 + 54321
TINY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=24, num_experts_per_tok=4,
            n_routed_experts=4, vocab_size=512, initializer_range=0.1,
            expert_parallel={"chips_per_layer": 4,
                             "published_n_routed_experts": 16,
                             "first_held_expert": 4})


def tiny(**kw) -> dict:
    conf = json.loads(CONF.read_text())
    conf.update(TINY, **kw)
    return conf


def test_layout_matches_the_program_at_a_tiny_size():
    conf = tiny()
    arch = common.arch(conf)
    cfg = arch.program_cfg(conf)
    assert [g.repeats for g in cfg.groups] == [1, 2]
    assert cfg.moe.held == (4, 4) and cfg.moe.n_experts == 16
    weights.check_layout(arch.layout(conf, 3),
                         weights.program_shapes(cfg, 3))
    # the router keeps every published expert; the chip stacks its own
    lay = arch.layout(conf, 3)
    assert lay["blocks/g1/slot0/moe/router"][0] == (2, 64, 16)
    assert lay["blocks/g1/slot0/moe/wi"][0] == (2, 4, 64, 24)
    with pytest.raises(ValueError, match="topk_method"):
        arch.program_cfg(tiny(topk_method="group_limited_greedy"))


def test_counts_worked_by_hand():
    conf = json.loads(CONF.read_text())
    m = common.arch(conf).Counts(conf)
    # MLA: q 2048 x 16*192, kv_a 2048 x 576, kv_b 512 x 16*256, o 2048 x 2048
    assert m.mla_params == 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert m.mla_params == 13_762_560
    # embedding and head 2 x 2048 x 102400; 27 MLA; one dense layer
    # 3 x 2048 x 10944; 26 x (8 held experts 3 x 2048 x 1408, shared 2 of
    # them, router 2048 x 64)
    want = 2 * 209_715_200 + 27 * 13_762_560 + 67_239_936 \
        + 26 * (8 * 8_650_752 + 17_301_504 + 131_072)
    assert m.params == want == 3_110_862_848
    # bf16, the embedding table gathered (not read), the router float32
    router = 26 * 131_072
    assert m.weight_bytes == (want - 209_715_200 - router) * 2 + router * 4
    # the latent: 512 + 64 values, bf16, 27 layers
    assert m.kv_bytes_per_token == 31_104
    # a decode row: matmuls with 0.75 routed experts a token, the head, and
    # absorbed attention 2 x keys x 16 x (2 x 512 + 64) in every layer
    per_token = 2 * (27 * 13_762_560 + 67_239_936
                     + 26 * (0.75 * 8_650_752 + 17_301_504 + 131_072)
                     + 209_715_200)
    assert m.token_flops() == int(per_token)
    assert m.decode_token_flops(99) == int(per_token) \
        + 2 * 100 * 16 * 1088 * 27
    flops, nbytes = m.decode_step([99, 9])
    assert nbytes == m.weight_bytes + (99 + 9 + 2) * 31_104
    # an expanded prefill of 4 tokens: 10 causal keys of 16 x (192 + 128)
    assert m.prefill_flops(4) == 4 * (int(per_token) - 2 * 209_715_200) \
        + 2 * 209_715_200 + 2 * 10 * 16 * 320 * 27


def test_reference_gaps_are_zero_on_its_own_tokens():
    """Tokens the reference itself puts first read gap 0; one token
    changed to another reads its logit's distance from the best."""
    import jax

    from bench.refs import deepseek_v2 as ref

    conf = tiny()
    layout = common.arch(conf).layout(conf, 2)
    key = common.jax_key(SEED, "weights")
    W = weights.flatten(weights.make(key, layout, conf["initializer_range"]))
    prompt = np.arange(10, 19, dtype=np.int32)
    seq = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(5):
            h = ref.forward(W, np.asarray(seq)[None], np.asarray([1]), conf, 4)
            seq.append(int(np.argmax(ref.logits(W, h[0, -1], conf))))
    served = np.asarray(seq[len(prompt):], np.int32)
    wrong = served.copy()
    wrong[2] = (wrong[2] + 1) % conf["vocab_size"]
    gaps, control = ref.served_gaps(
        conf, layout, key, conf["initializer_range"],
        [(prompt, served, 1), (prompt, wrong, 1)], length=16, max_new=8,
        control=True)
    assert float(np.abs(gaps[0]).max()) == 0.0
    assert gaps[1][2] > 0 and float(np.abs(gaps[1][:2]).max()) == 0.0
    assert len(control) == 2 and control[0].shape == (5,)


def make_tiny_root(dst: pathlib.Path) -> pathlib.Path:
    """The committed benchmark with the new configuration and its traffic
    cut to CPU size. The mean-gap limit is set for this size from its
    own readings on the CPU, where float32 matmuls are exact: the program
    reads below 1e-5, the fp8 control above 1e-3."""
    shutil.copy(REPO / "BENCHMARK.json", dst)
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(REPO / "src")
    (dst / "bench/configs/deepseek-v2-lite-ep8.json").write_text(
        json.dumps(tiny(dtype={"param": "float32", "compute": "float32",
                               "adapter": "float32"})))
    tr = json.loads((dst / "bench/traffic/chat-decode.json").read_text())
    tr["serving"].update(num_slots=4, max_len=128, prefill_bucket=32,
                         num_blocks=41)
    tr["prompt_len"].update(median=24, min=4, max=64)
    tr["output_len"].update(median=12, min=4, max=48)
    tr["arrivals"]["rate_rps"] = 4.0
    tr["tenants"]["n"] = 4
    tr.update(lead_in_s=0.5, trace={"start_frac": 0.4, "length_s": 0.5})
    tr["check"] = {"served_tokens": 64, "max_requests": 4,
                   "limits": {"mean_logit_gap": 1e-3}}
    (dst / "bench/traffic/chat-decode.json").write_text(json.dumps(tr))
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("dsv2_root"))


def test_the_cell_loads_through_the_lookup():
    bench, w, conf, traffic = run.load_cell(REPO, CELL)
    assert w["chips"] == 1 and conf["model_type"] == "deepseek_v2"
    assert common.arch(conf, REPO).__name__ == "bench_arch_deepseek_v2"
    assert traffic["serving"]["num_slots"] == 128
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms", "serve_tok_s",
                         "tick_ms.chat", "decode_roofline.chat",
                         "prefill_roofline.chat", "mfu.chat",
                         "idle_share.chat"):
            assert run.applies(m, CELL), m["name"]
        elif m["name"] != "setup_s":
            assert not run.applies(m, CELL), m["name"]


@pytest.mark.parametrize("kind", ["program", "control", "insert_skipped"])
def test_the_cell_runs_and_correct_tells_the_program_from_faults(
        tiny_root, kind):
    kw = {"control": True} if kind == "control" else (
        {"fault": kind} if kind != "program" else {})
    out = run.run_cell(tiny_root, CELL, SEED, 2.0, kind == "program",
                       require_chip=False, **kw)
    if kind == "program":
        assert out["correct"], out["checks"]
        assert out["attempted"] > 0 and out["failed"] == 0
        assert "tick_ms.chat" in out["metrics"]  # the rest need a peak
    else:
        assert not out["correct"], out["checks"]
