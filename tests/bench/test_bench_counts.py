"""Each architecture module's `Counts`, found through `common.arch` as
the drivers find it, against counts worked by hand from the published
widths."""
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.append(str(REPO))  # the harness is the package `bench` there

from bench import common  # noqa: E402


def counts(name):
    conf = json.loads((REPO / "bench/configs" / f"{name}.json").read_text())
    return common.arch(conf).Counts(conf)


def test_qwen3_weights_and_kv():
    m = counts("qwen3-0.6b")
    # per layer: q 1024x2048, k and v 1024x1024 each, o 2048x1024,
    # gate, up, down 1024x3072 each
    assert m.layer_params == 1024 * 4096 + 2048 * 1024 + 3 * 1024 * 3072
    assert m.layer_params == 15_728_640
    # 28 layers plus the tied head (1024 x 151936), bf16
    assert m.weight_bytes == (28 * 15_728_640 + 155_582_464) * 2
    assert m.weight_bytes == 1_191_968_768
    # K and V, 8 heads x 128, bf16, 28 layers
    assert m.kv_bytes_per_token == 114_688


def test_qwen3_decode_step():
    m = counts("qwen3-0.6b")
    flops, nbytes = m.decode_step([100, 200])
    # each row needs 2 x (440_401_920 + 155_582_464) FLOPs of matmul and
    # head, and attention 4 x keys x 16 x 128 x 28 over 101 and 201 keys
    assert flops == 2 * 2 * 595_984_384 + 4 * (101 + 201) * 2048 * 28
    assert flops == 2_453_209_088
    # weights once, the live KV below each row's position, one new entry
    # per row
    assert nbytes == 1_191_968_768 + (100 + 200) * 114_688 + 2 * 114_688
    assert nbytes == 1_226_604_544


def test_qwen3_prefill():
    m = counts("qwen3-0.6b")
    flops, nbytes = m.prefill(256)
    # 256 tokens through the layers, the head at the last one, causal
    # attention over 256 x 257 / 2 query-key pairs
    assert flops == (2 * 256 * 440_401_920 + 2 * 155_582_464
                     + 4 * (256 * 257 // 2) * 2048 * 28)
    assert flops == 233_342_500_864
    assert nbytes == 1_191_968_768 + 256 * 114_688


def test_roberta_large_train_token():
    m = counts("roberta-large")
    # q, k, v, o 1024^2 each and the 1024x4096 MLP pair: 302 M weights
    assert 24 * m.layer_params == 301_989_888
    fwd = 2 * 301_989_888 + 4 * 128 * 1024 * 24 + 2 * (1024 * 1024 + 2048) / 128
    bwd = 2 * 301_989_888 + 8 * 128 * 1024 * 24 + 2 * (1024 * 1024 + 2048) / 128
    assert m.train_token_flops(128) == fwd + bwd + 4 * 1024 * 24
    assert round(m.train_token_flops(128)) == 1_245_839_424
