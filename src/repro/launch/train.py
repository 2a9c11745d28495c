"""Training launcher.

Examples:
  # LM fine-tune with the Hadamard adapter on a reduced arch (CPU-runnable):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --peft hadamard --steps 50

  # paper two-stage GLUE-style fine-tune on a BERT-family encoder:
  PYTHONPATH=src python -m repro.launch.train --arch bert-small --task sst2 \
      --peft hadamard --steps 200
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.common.runtime import init_compile_cache
from repro.common.types import OptimCfg, TrainCfg
from repro.configs import PAPER, get, get_smoke
from repro.core import peft
from repro.data.pipeline import Prefetcher, shard_batches
from repro.data.synthetic import TASKS, TaskData, lm_batches, lm_corpus
from repro.dist.api import use_mesh
from repro.launch.mesh import parse_mesh
from repro.launch.pretrain import QUANT_PRESETS
from repro.optim import qstate
from repro.train.loop import StepWatchdog, run_train, two_stage_finetune
from repro.train.steps import build_train_step, make_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--peft", default="hadamard",
                    choices=sorted(peft.STRATEGIES))
    ap.add_argument("--task", default=None, choices=sorted(TASKS),
                    help="GLUE-style task (encoder archs); default: LM data")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--quant-moments", default="",
                    choices=sorted(QUANT_PRESETS),
                    help="AdamW moment storage (repro.optim.qstate): "
                         "bf16 / bf16+int8 / int8. Matters most with "
                         "--peft full, where the moments are the memory "
                         "ceiling; '' keeps exact fp32 moments")
    ap.add_argument("--no-ef", action="store_true",
                    help="disable int8 moment error feedback (bytes floor "
                         "only - no-EF int8 v deadzones and diverges)")
    ap.add_argument("--prune-to", type=int, default=0,
                    help="repro.sparse: train only the top-K layers' "
                         "adapters (mask-gated gradients; the rest stay "
                         "identity and pack away at publish time). 0 = all "
                         "layers; the paper's 0.022%% variant is K = 2L/3")
    ap.add_argument("--quant", default="", choices=["", "int8", "fp8"],
                    help="QPEFT: quantize the frozen trunk (int8/fp8) and "
                         "train the fp32 adapter on top of it "
                         "(decoder-LM path; needs a frozen-trunk strategy)")
    ap.add_argument("--calibrate-batches", type=int, default=0,
                    help="with --quant: run this many batches of "
                         "activation-statistics calibration before "
                         "quantizing (0 = plain absmax scales)")
    ap.add_argument("--mesh", default="",
                    help="'DATAxMODEL' (e.g. 2x4): train SPMD on a host "
                         "mesh (pair with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    args = ap.parse_args()
    init_compile_cache()

    mesh = parse_mesh(args.mesh)
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    strat = peft.strategy(args.peft)
    m_dt, v_dt = QUANT_PRESETS[args.quant_moments]
    ocfg = OptimCfg(lr=args.lr, total_steps=args.steps,
                    compress_grads=args.compress_grads,
                    m_dtype=m_dt, v_dtype=v_dt, qstate_ef=not args.no_ef)

    layer_mask = None
    if args.prune_to:
        from repro.sparse.importance import depth_mask, n_layers

        try:
            layer_mask = depth_mask(cfg, args.prune_to)
        except ValueError as e:
            raise SystemExit(f"--prune-to: {e}")
        print(f"pruned training: top {args.prune_to}/{n_layers(cfg)} "
              "layers' adapters unfrozen (mask-gated gradients)")

    if cfg.family == "encoder":
        if args.quant:
            raise SystemExit("--quant targets the decoder-LM path; the "
                             "two-stage encoder recipe manages its own "
                             "states (quantize post-training for serving)")
        task = args.task or "sst2"
        data = TaskData(task, cfg.vocab_size, seq_len=args.seq, seed=args.seed)
        tc = TrainCfg(optim=ocfg, steps=args.steps, batch_size=args.batch,
                      seq_len=args.seq, log_every=10)
        res = two_stage_finetune(
            jax.random.PRNGKey(args.seed), cfg, args.peft, data,
            stage1=tc, stage2=tc, metric=TASKS[task].metric,
            layer_mask=layer_mask)
        print(f"final {TASKS[task].metric}: {res['final_metric']:.4f}")
        return

    # decoder-family LM fine-tuning with PEFT (optionally SPMD over a mesh)
    cfg = peft.attach(cfg, strat)
    corpus = lm_corpus(cfg.vocab_size, 200_000, seed=args.seed)
    source = lm_batches(corpus, args.steps, args.batch, args.seq,
                        seed=args.seed)
    if mesh is not None:
        source = shard_batches(source, mesh)  # sharded device_put on the dp axes
    batches = Prefetcher(source)
    with use_mesh(mesh):  # use_mesh(None) is a no-op
        params = stats = None
        if args.quant and args.calibrate_batches:
            from repro.models import model as M
            from repro.quant import calibrate

            params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
            cal = lm_batches(corpus, args.calibrate_batches, args.batch,
                             args.seq, seed=args.seed + 1)
            stats = calibrate(cfg, params, cal,
                              max_batches=args.calibrate_batches)
            print(f"calibrated {len(stats)} call sites over "
                  f"{args.calibrate_batches} batches")
        state = make_state(jax.random.PRNGKey(args.seed), cfg, strat, ocfg,
                           params=params, quant=args.quant or None,
                           quant_stats=stats)
        if qstate.quantized_moments(ocfg):
            qss = qstate.state_summary(state["opt"], ocfg)
            print(f"optimizer state: {qss['bytes'] / 2**20:.2f} MiB for "
                  f"{qss['n_params']:,} params (fp32 would be "
                  f"{qss['bytes_fp32'] / 2**20:.2f} MiB; "
                  f"{qss['ratio']:.2f}x)")
        if args.quant:
            from repro.quant import quant_summary

            qs = quant_summary(state["frozen"])
            print(f"quantized trunk: {qs['n_quantized_leaves']} leaves, "
                  f"{qs['dense_bytes_fp32'] / 2**20:.1f} MiB fp32 -> "
                  f"{qs['quantized_bytes'] / 2**20:.1f} MiB "
                  f"({qs['ratio']:.2f}x)")
        manager = None
        if args.ckpt_dir:
            manager = CheckpointManager(args.ckpt_dir, keep=3)
            if args.resume and manager.latest() is not None:
                from repro.checkpoint import restore_into

                restored, meta = manager.restore()
                state = restore_into(state, restored)
                print(f"resumed from step {meta['step']}")
        step = build_train_step(cfg, ocfg, layer_mask=layer_mask)
        state, hist = run_train(state, step, batches, steps=args.steps,
                                log_every=10, manager=manager,
                                save_every=args.save_every,
                                watchdog=StepWatchdog())
    print(f"final loss: {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
