"""CPU rehearsal of `chip_smoke.py`: its phases run end to end on smoke
configs (interpret-mode kernels, CPU devices), its checks can fail, and
the script refuses to report a result without a TPU. Also the two
no-fallback pieces it relies on: `make_host_mesh` and the compile-cache
helper."""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.common import runtime
from repro.configs import get_smoke
from repro.core import peft
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import build_params
from repro.serving import MultiTaskEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _qwen():
    return peft.attach(get_smoke("qwen3-0.6b"), peft.strategy("hadamard"))


def test_serve_phase_on_cpu(cs, capsys):
    cs.serve_phase(_qwen(), prompt_len=16, new_tokens=4)
    out = capsys.readouterr().out
    for what in ("token-identical to engine.generate", "zero retrace",
                 "prefill logits of task 1", "top-1 equals",
                 "another task's logits lie outside", "tok/s"):
        assert what in out


def test_int8_phase_on_cpu(cs, capsys, monkeypatch):
    """The phase as the chip runs it; on CPU the Pallas kernel runs in the
    interpreter and the decode step has no TPU kernel to find."""
    dequant = cs.ops.dequant_matmul

    def interpreted(*a, impl="auto", **kw):
        return dequant(*a, impl="interpret" if impl == "pallas" else impl,
                       **kw)

    monkeypatch.setattr(cs.ops, "dequant_matmul", interpreted)
    monkeypatch.setattr(cs, "decode_has_kernel", lambda *a: True)
    cs.int8_phase(_qwen(), prompt_len=16, new_tokens=4)
    out = capsys.readouterr().out
    assert "dequant_matmul pallas within" in out
    assert "tok/s" in out


def test_kernel_check_fails_off_the_kernel_path(cs):
    """On CPU the int8 decode step runs the jnp dequant: the check that
    guards the chip run must see no Pallas kernel there."""
    cfg = _qwen()
    _, variants = build_params(jax.random.PRNGKey(0), cfg, 2)
    engine = MultiTaskEngine(cfg, variants, quant="int8")
    assert not cs.decode_has_kernel(engine, 2, 16)


def test_train_phase_on_cpu(cs, capsys):
    cs.train_phase(get_smoke("bert-base"), batch=8, seq=32, steps=3)
    out = capsys.readouterr().out
    assert "frozen backbone bit-unchanged" in out
    assert "adapter leaves moved" in out


def test_failed_check_raises(cs):
    with pytest.raises(cs.SmokeFailure, match="the thing"):
        cs.check(False, "the thing")


def test_main_refuses_without_tpu(cs, capsys):
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


def test_sharded_phase_on_four_cpu_devices():
    """The --chips 4 path at smoke size, in a child process that forces
    four CPU devices (this process keeps its one-device view)."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('cs', {SMOKE!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "from repro.configs import get_smoke\n"
        "from repro.core import peft\n"
        "q = peft.attach(get_smoke('qwen3-0.6b'), peft.strategy('hadamard'))\n"
        "cs.sharded_phase(q, get_smoke('bert-base'), prompt_len=16,\n"
        "                 new_tokens=4, seq=32)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "split over 4 devices" in r.stdout
    assert "sharded greedy tokens equal" in r.stdout
    assert "SPMD step loss matches" in r.stdout
    assert "SPMD step's gradient matches" in r.stdout
    assert "half batch's gradient (a missing all-reduce) lies outside" \
        in r.stdout


def test_make_host_mesh_raises_on_too_few_devices():
    assert len(jax.devices()) == 1
    with pytest.raises(ValueError, match="needs 8 devices; 1 available"):
        make_host_mesh(2, 4)
    assert make_host_mesh(1, 1).devices.shape == (1, 1)


def test_compile_cache_honours_env(monkeypatch):
    """The helper leaves JAX's own reading of JAX_COMPILATION_CACHE_DIR
    alone, and otherwise sets the fixed checkout path (config updates are
    recorded, not applied; nothing compiles)."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(runtime.CACHE_ENV, "/elsewhere/cache")
    assert runtime.init_compile_cache() == "/elsewhere/cache"
    assert updates == []

    monkeypatch.delenv(runtime.CACHE_ENV)
    want = os.path.join(REPO, ".jax_cache")
    assert runtime.init_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_compile_cache_dir_is_ignored_by_git():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    assert str(runtime.CHECKOUT) == REPO
