"""Whole serving step: the FLOPs the algorithm needs for the tokens the
window served (each prompt's causal prefill, each generated token's
decode at its own position), per second of the window, over the chip's
bf16 peak. Arrivals are open-loop at a fixed rate, so the profiler's
stall in a traced run delays work that the window still completes: the
whole window is the right denominator here (the untraced runs log the
same number, and read alike)."""


def read(record):
    peak = record["peak"]
    if not record.get("window_flops") or not peak:
        return None
    return 100.0 * record["window_flops"] / record["window_s"] \
        / peak["bf16_flops"]
