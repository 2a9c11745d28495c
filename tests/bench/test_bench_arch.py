"""A weight layout of two groups, a leading dense layer and then layers
with a mixture-of-experts FFN (a router, routed `wi`/`wg`/`wo` and
shared experts), as a DeepSeek architecture module would state it, held
against the program's own parameter tree at a tiny size."""
import json
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.append(str(REPO))  # the harness is the package `bench` there

from bench import common, weights  # noqa: E402

TENANTS, EXPERTS, TOP_K, D_EXPERT, SHARED = 3, 4, 2, 32, 2


def tiny_qwen3() -> dict:
    conf = json.loads((REPO / "bench/configs/qwen3-0.6b.json").read_text())
    conf.update(hidden_size=64, intermediate_size=128, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=512)
    return conf


def moe_program(conf):
    """The program's config: layer 0 dense, layers 1-2 of experts."""
    from repro.common.types import Group, MoECfg, Slot

    return common.arch(conf).program_cfg(conf).replace(
        groups=(Group((Slot("attn"),), 1),
                Group((Slot("attn", moe=True),), 2)),
        moe=MoECfg(n_experts=EXPERTS, top_k=TOP_K, d_expert=D_EXPERT,
                   n_shared=SHARED))


def moe_layout(conf) -> dict:
    """qwen3's layout in the same two groups, the second group's dense MLP
    replaced by its experts."""
    layout = common.arch(conf).layout(conf, TENANTS, (1, 2))
    d, p = conf["hidden_size"], conf["dtype"]["param"]
    E, f, sf = EXPERTS, D_EXPERT, SHARED * D_EXPERT
    S = weights.stack(1)
    for n in ("wi", "wg", "wo"):
        del layout[S + "mlp/" + n]
    layout.update({
        S + "moe/router": ((2, d, E), "float32", "normal"),
        S + "moe/wi": ((2, E, d, f), p, "normal"),
        S + "moe/wg": ((2, E, d, f), p, "normal"),
        S + "moe/wo": ((2, E, f, d), p, "normal"),
        S + "moe/shared_wi": ((2, d, sf), p, "normal"),
        S + "moe/shared_wg": ((2, d, sf), p, "normal"),
        S + "moe/shared_wo": ((2, sf, d), p, "normal"),
    })
    return layout


def test_a_two_group_moe_layout_matches_the_program():
    conf = tiny_qwen3()
    shapes = weights.program_shapes(moe_program(conf), TENANTS)
    weights.check_layout(moe_layout(conf), shapes)
    # the dense two-group layout is not the MoE program's tree
    with pytest.raises(ValueError, match="differs"):
        weights.check_layout(
            common.arch(conf).layout(conf, TENANTS, (1, 2)), shapes)
    # and the reference reads the groups in order, each of its depth
    flat = {path: np.zeros(shape) for path, (shape, _, _)
            in moe_layout(conf).items()}
    groups = weights.by_group(flat)
    assert [g["attn/wq"].shape[0] for g in groups] == [1, 2]
    assert "mlp/wi" in groups[0] and "moe/router" in groups[1]
