"""Paged-attention kernel's share of its roofline, in %.

For every decode call in the window, the least time the chip could take
for the kernel's work -- the larger of FLOPs / bf16 peak and bytes /
HBM bandwidth, from the real lengths of the active rows (bench.flops),
times the layers -- summed, over the kernel's device time in the trace.
At these shapes the bytes bound (about 7 FLOP a byte)."""
from bench import flops, peaks, trace


def read(r):
    calls = r["calls"].get("bench.decode") or []
    t_kernel = sum(trace.op_seconds(r["trace"], r["lo"], r["hi"],
                                    trace.PAGED_KERNEL).values())
    if not calls or t_kernel <= 0:
        return None
    pk = peaks.peak(r["device_kind"])
    least = 0.0
    for lengths in calls:
        f, b = flops.paged_attention_cost(
            r["dims"], [n + 1 for n in lengths], r["kv_bytes"])
        least += r["dims"].n_layers * max(f / pk["bf16_flops"],
                                          b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t_kernel
