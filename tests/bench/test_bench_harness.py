"""The harness end to end on the CPU, at the sizes of `make_tiny_root`:
the look for a chip refuses the CPU, a new cell, a new per-layer metric
and a new architecture are taken from added files alone, a configuration
with no architecture module is refused, and `correct` comes out false
for the control (one precision lower) and for each fault the cell can
have, planted under the timed path."""
import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.append(str(REPO))  # the harness is the package `bench` there

from bench import run  # noqa: E402

SEED = 2**31 + 12345  # more than 32 signed bits, as the driver's seeds are
CHAT, TRAIN = "qwen3-0.6b.chat", "roberta-large.glue-train"


def edit(path, **kw):
    data = json.loads(path.read_text())
    for key, value in kw.items():
        if isinstance(value, dict):
            data[key].update(value)
        else:
            data[key] = value
    path.write_text(json.dumps(data))


def make_tiny_root(dst: pathlib.Path) -> pathlib.Path:
    """A checkout of the benchmark cut to CPU size: the committed
    `BENCHMARK.json` and `bench/`, the configurations shrunk (same
    architectures, two layers, d 64) and the traffic scaled down.

    The serving limit is set for this size from its own readings on the
    CPU, where float32 matmuls are exact (the chip's default pass is
    bfloat16): the mean logit gap reads 0.00016 for the program, 0.0034
    for the fp8 control and 0.11 or more for the planted serving faults,
    so 0.001. The training limits are the chip's: the first step's loss
    gap reads about 1e-7 here and 0.2 or more for a half batch, the first
    gradient's norm gap 1e-6 and 1.5 or more, the change norm gap 1e-6
    and about 1 for the bfloat16 control."""
    shutil.copy(REPO / "BENCHMARK.json", dst)
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(REPO / "src")
    small = dict(initializer_range=0.1, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4, vocab_size=512)
    edit(dst / "bench/configs/qwen3-0.6b.json", num_key_value_heads=2,
         head_dim=16, **small)
    edit(dst / "bench/configs/roberta-large.json", max_position_embeddings=64,
         **small)
    edit(dst / "bench/traffic/chat.json",
         serving={"num_slots": 4, "max_len": 128, "prefill_bucket": 32},
         prompt_len={"median": 24, "min": 4, "max": 64},
         output_len={"median": 12, "min": 4, "max": 48},
         arrivals={"rate_rps": 4.0}, tenants={"n": 4}, lead_in_s=0.5,
         trace={"length_s": 0.5},
         check={"served_tokens": 64, "max_requests": 4,
                "limits": {"mean_logit_gap": 1e-3}})
    edit(dst / "bench/traffic/glue-train.json", batch=8, seq=32, rows=256,
         trace={"length_s": 0.5})
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))


def run_tiny(root, cell, **kw):
    kw.setdefault("seconds", 2.0)
    return run.run_cell(root, cell, SEED, kw.pop("seconds"),
                        kw.pop("trace", False), require_chip=False, **kw)


def test_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", CHAT, "--seed", str(SEED),
                     "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_a_new_cell_and_metric_from_added_files_alone(tiny_root, tmp_path):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root, symlinks=True)
    # a new traffic mix: the chat mix at another rate, as a data file
    chat = json.loads((root / "bench/traffic/chat.json").read_text())
    chat["arrivals"]["rate_rps"] = 3.0
    (root / "bench/traffic/chat-slow.json").write_text(json.dumps(chat))
    # a new per-layer metric: its reader, a file of its own
    (root / "bench/metrics/ticks.chat-slow.py").write_text(
        "def read(record):\n    return record['ticks']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "qwen3-0.6b.chat-slow",
                               "config": "qwen3-0.6b", "traffic": "chat-slow",
                               "chips": 1, "why": "a slower chat"})
    for m in bench["end_to_end"]:
        if "qwen3-0.6b.chat" in m.get("workloads", []):
            m["workloads"].append("qwen3-0.6b.chat-slow")
    bench["per_layer"].append({"name": "ticks.chat-slow", "unit": "ticks",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "serve_tok_s",
                               "workloads": ["qwen3-0.6b.chat-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = run_tiny(root, "qwen3-0.6b.chat-slow")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "serve_tok_s", "setup_s"}
    traced = run_tiny(root, "qwen3-0.6b.chat-slow", trace=True)
    assert traced["metrics"]["ticks.chat-slow"]["value"] > 0
    assert "tick_ms.chat" not in traced["metrics"]  # another cell's
    assert list(traced)[-1] == "checks"


# An architecture that lays qwen3's layers out in two groups, a leading
# layer and then the rest, as DeepSeek's `first_k_dense_replace` does.
LEAD_ARCH = '''"""qwen3 with its first layer in a group of its own."""
from bench.arch import qwen3
from bench.arch.qwen3 import Counts, served_gaps  # noqa: F401


def groups(conf):
    return (1, conf["num_hidden_layers"] - 1)


def program_cfg(conf):
    return qwen3.program_cfg(conf, groups(conf))


def layout(conf, tenants):
    return qwen3.layout(conf, tenants, groups(conf))
'''


def test_a_new_architecture_from_added_files_alone(tiny_root, tmp_path):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root, symlinks=True)
    committed = {p for p in root.rglob("*") if p.is_file()}
    (root / "bench/arch/qwen3_lead.py").write_text(LEAD_ARCH)
    conf = json.loads((root / "bench/configs/qwen3-0.6b.json").read_text())
    conf.update(name="qwen3-lead", model_type="qwen3_lead",
                num_hidden_layers=3)
    (root / "bench/configs/qwen3-lead.json").write_text(json.dumps(conf))
    shutil.copy(root / "bench/traffic/chat.json",
                root / "bench/traffic/chat-lead.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen3-lead", "source": "a test",
                             "file": "bench/configs/qwen3-lead.json",
                             "reduced": [], "why": "two groups of layers"})
    bench["workloads"].append({"name": "qwen3-lead.chat-lead",
                               "config": "qwen3-lead", "traffic": "chat-lead",
                               "chips": 1, "why": "a leading layer"})
    for m in bench["end_to_end"]:
        if CHAT in m.get("workloads", []):
            m["workloads"].append("qwen3-lead.chat-lead")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # BENCHMARK.json takes entries; every other file is new
    changed = {p for p in committed if p.name != "BENCHMARK.json"
               and p.read_bytes() != (tiny_root / p.relative_to(root))
               .read_bytes()}
    assert not changed

    from bench import common
    cfg = common.arch(conf, root).program_cfg(conf)
    assert [g.repeats for g in cfg.groups] == [1, 2]
    out = run_tiny(root, "qwen3-lead.chat-lead")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    faulty = run_tiny(root, "qwen3-lead.chat-lead", fault="insert_skipped")
    assert not faulty["correct"], faulty["checks"]


def test_a_configuration_without_its_module_is_refused(tiny_root, tmp_path):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root, symlinks=True)
    edit(root / "bench/configs/qwen3-0.6b.json", model_type="no_such_arch")
    with pytest.raises(run.common.BenchError,
                       match="bench/arch/no_such_arch.py"):
        run_tiny(root, CHAT)


def test_chat_cell_is_correct(tiny_root):
    out = run_tiny(tiny_root, CHAT)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["serve_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["token_altered", "insert_skipped"])
def test_chat_fault_is_caught(tiny_root, fault):
    out = run_tiny(tiny_root, CHAT, fault=fault)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_train_cell_is_correct(tiny_root):
    out = run_tiny(tiny_root, TRAIN, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_caught(tiny_root, fault):
    out = run_tiny(tiny_root, TRAIN, seconds=1.0, fault=fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", [CHAT, TRAIN])
def test_control_is_not_correct(tiny_root, cell):
    out = run_tiny(tiny_root, cell, seconds=1.0, control=True)
    assert not out["correct"], out["checks"]
