import os

# Tests run on the single real CPU device; only subprocess-based
# distribution tests force multiple host devices (in their own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def tiny_cfg(**kw):
    """Shared tiny decoder config for unit tests."""
    from repro.common.types import AdapterCfg, Group, ModelCfg, Slot

    base = dict(
        name="tiny",
        family="decoder",
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        vocab_size=97,
        groups=(Group((Slot("attn"),), 2),),
        param_dtype="float32",
        compute_dtype="float32",
        max_seq_len=64,
        adapter=AdapterCfg(kind="hadamard"),
        q_chunk=8,
        kv_chunk=8,
        sequence_sharding=False,
    )
    base.update(kw)
    return ModelCfg(**base)


def stacked_groups():
    """Parameters over layer groups whose decode indexes the stacked caches
    at several layers and slots, so a read or write at the wrong layer or
    slot changes the logits: the tiny default, a deeper stack, and two
    attention slots per layer."""
    from repro.common.types import Group, Slot

    return [pytest.param(Group((Slot("attn"),), 2), id="repeats2"),
            pytest.param(Group((Slot("attn"),), 3), id="repeats3"),
            pytest.param(Group((Slot("attn"), Slot("attn")), 2),
                         id="two_slots")]


def record_kv_tiles(monkeypatch):
    """A list that collects ((Sq, Skv), (q chunk, kv chunk)) for every
    flash call traced from here on (`flash._kv_tile`'s choice)."""
    from repro.models import flash

    tiles, choose = [], flash._kv_tile

    def spy(*a):
        tiles.append((a[:2], choose(*a)))
        return tiles[-1][1]
    monkeypatch.setattr(flash, "_kv_tile", spy)
    return tiles
