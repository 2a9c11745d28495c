"""The prefill program's share of its roofline: the least time for the
unpadded prompt tokens of each traced prefill (FLOPs of a causal forward
with logits for the last position only, against the bf16 peak; bytes of
the weights once and the KV written, against HBM bandwidth), averaged
over the traced prefills, over the prefill program's mean device time
per execution. Bucket padding is work the count does not credit."""
PROGRAM = "jit__pf"


def read(record):
    times = record["trace"].get("programs", {}).get(PROGRAM)
    lengths = record.get("prefill_lengths", [])
    peak = record["peak"]
    if not times or not lengths or not peak:
        return None
    model = record["counts"]
    least = []
    for S in lengths:
        flops, nbytes = model.prefill(S)
        least.append(max(flops / peak["bf16_flops"],
                         nbytes / peak["hbm_bytes_per_s"]))
    return 100.0 * (sum(least) / len(least)) / (sum(times) / len(times))
