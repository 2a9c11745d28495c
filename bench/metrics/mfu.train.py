"""Whole train step: tokens trained per second times the FLOPs each
token needs (the architecture module's `Counts`: forward, the frozen
backbone's activation gradients, the trainable leaves' gradients), over
the chip's bf16 peak. The rate is taken outside the traced stretch,
between step completions, so the profiler's start and stop do not enter
it."""


def read(record):
    peak = record["peak"]
    if not record.get("outside_steps") or not peak:
        return None
    return 100.0 * record["outside_steps"] * record["tokens_per_step"] \
        * record["flops_per_token"] / record["outside_s"] / peak["bf16_flops"]
