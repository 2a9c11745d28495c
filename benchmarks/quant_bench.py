"""Quantized-backbone pricing: what does int8 (and fp8, where the build
ships the dtype) buy on the serving path?

Three questions, three row groups:

  * `quant/bytes_*`   - parameter-byte accounting: fp32 backbone vs
    QTensor (int8 payload + per-channel fp32 scales). This is the
    multi-tenant headline: the compressed base is shared by every tenant
    while each task stays a KB-sized fp32 adapter row.
  * `quant/prefill_*` / `quant/decode_*` - per-call latency of the jitted
    prefill and the fused decode tick, fp32 vs quantized.
  * `quant/serve_*`   - end-to-end scheduler tok/s over the same request
    stream, fp32 vs quantized (greedy, so the comparison is token-exact
    work, not just wall clock).

The model is sized so matmul weights dominate (tied embeddings, 4 layers,
d=128): the bytes ratio must clear the >= 3.5x acceptance line with the
fp32 scale and unquantized-embedding overheads included.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import record, timed


def _bench_cfg(fast: bool):
    from repro.common.types import AdapterCfg, Group, ModelCfg, Slot

    layers = 4 if fast else 8
    return ModelCfg(
        name="quant-bench", family="decoder", d_model=128, n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=512, vocab_size=97,
        groups=(Group((Slot("attn"),), layers),),
        param_dtype="float32", compute_dtype="float32",
        tie_embeddings=True, max_seq_len=128,
        adapter=AdapterCfg(kind="hadamard"),
        q_chunk=32, kv_chunk=32, sequence_sharding=False)


def _serve_tok_s(engine, prompts, budget: int, num_slots: int,
                 max_len: int) -> float:
    from repro.serving import Request, ServingConfig, make_scheduler

    sched = make_scheduler(engine, ServingConfig(num_slots=num_slots,
                                                 max_len=max_len))
    reqs = [Request(prompt=p, max_new_tokens=budget) for p in prompts]
    t0 = time.perf_counter()
    _, report = sched.run(reqs)
    del t0
    return report["tokens_per_s"]


def run(fast: bool = True) -> None:
    from repro.models import model as M
    from repro.quant import quant_summary
    from repro.serving.engine import ServeEngine

    cfg = _bench_cfg(fast)
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)

    modes = ["int8", "fp8"]

    n_req, plen, budget = (8, 16, 8) if fast else (32, 64, 32)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(10, cfg.vocab_size, size=(plen,))
               for _ in range(n_req)]
    max_len = plen + budget
    toks = np.stack([p for p in prompts[:4]])

    engines = {"fp32": ServeEngine(cfg, params)}
    for m in modes:
        engines[m] = ServeEngine(cfg, params, quant=m)

    # --- bytes ---
    base = quant_summary(engines["fp32"].params)["total_bytes"]
    for m in modes:
        qs = quant_summary(engines[m].params)
        backbone_ratio = base / qs["total_bytes"]
        record(f"quant/bytes_{m}", 0.0,
               f"backbone {base / 2**20:.2f}->"
               f"{qs['total_bytes'] / 2**20:.2f}MiB "
               f"({backbone_ratio:.2f}x; matmul-leaves {qs['ratio']:.2f}x "
               f"over {qs['n_quantized_leaves']} leaves)")

    # KV-cache bytes ride along: on the paged serving path the cache is a
    # block pool, and quantized blocks shrink it independently of the
    # backbone (per-token scales vs per-channel weight scales)
    from repro.quant.qtensor import is_qtensor

    def kv_bytes(quant):
        pool = M.init_paged_pool(cfg, num_blocks=9, page=16, quant=quant)
        return sum(
            (leaf.values.nbytes + leaf.scales.nbytes)
            if is_qtensor(leaf) else leaf.nbytes
            for leaf in jax.tree.leaves(pool, is_leaf=is_qtensor))

    kv32 = kv_bytes(None)
    for m in modes:
        record(f"quant/kv_bytes_{m}", 0.0,
               f"paged KV pool {kv32 / 2**20:.3f}->"
               f"{kv_bytes(m) / 2**20:.3f}MiB "
               f"({kv32 / kv_bytes(m):.2f}x at 8 blocks of 16 tokens)")

    # --- prefill / decode latency ---
    lat = {}
    for name, eng in engines.items():
        _, us = timed(lambda e=eng: jax.block_until_ready(
            e.prefill(toks, max_len)[0]))
        lat[f"prefill_{name}"] = us
        logits, caches = eng.prefill(toks, max_len)
        tok = np.argmax(np.asarray(logits)[:, -1], axis=-1).astype(np.int32)
        cell = {"c": caches, "pos": plen}

        def one_decode(e=eng, t=tok):
            # decode donates its caches: thread them through the cell so
            # every timed call is a real (donation-valid) decode tick
            out, cell["c"] = e.decode_step(cell["c"], t[:, None],
                                           np.int32(cell["pos"]))
            cell["pos"] += 1
            jax.block_until_ready(out)
            return out

        _, us = timed(one_decode)
        lat[f"decode_{name}"] = us
    for name, us in lat.items():
        base_us = lat[name.split("_")[0] + "_fp32"]
        record(f"quant/{name}", us, f"{base_us / max(us, 1e-9):.2f}x_vs_fp32")

    # --- end-to-end serve throughput ---
    tok_s = {}
    for name, eng in engines.items():
        tok_s[name] = _serve_tok_s(eng, prompts, budget, num_slots=4,
                                   max_len=max_len)
        record(f"quant/serve_{name}",
               1e6 / max(tok_s[name], 1e-9),
               f"{tok_s[name]:.1f}tok/s "
               f"({tok_s[name] / max(tok_s['fp32'], 1e-9):.2f}x_vs_fp32)")
