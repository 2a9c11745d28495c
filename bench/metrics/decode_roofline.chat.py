"""The paged decode step's share of its roofline: the least time the
chip needs for the work each traced step needs (the larger of FLOPs over
the bf16 peak and bytes over HBM bandwidth; bytes are the weights once,
each active row's live KV below its position and one new KV entry per
row, with no `max_len` padding), averaged over the traced steps, over
the step program's mean device time per execution in the trace."""
PROGRAM = "jit__pdc"


def read(record):
    times = record["trace"].get("programs", {}).get(PROGRAM)
    steps = [p for p in record.get("decode_positions", []) if p]
    peak = record["peak"]
    if not times or not steps or not peak:
        return None
    model = record["counts"]
    least = []
    for positions in steps:
        flops, nbytes = model.decode_step(positions)
        least.append(max(flops / peak["bf16_flops"],
                         nbytes / peak["hbm_bytes_per_s"]))
    return 100.0 * (sum(least) / len(least)) / (sum(times) / len(times))
