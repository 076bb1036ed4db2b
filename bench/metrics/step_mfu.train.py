"""Whole-step model FLOP/s utilization of a training window, in %.

FLOPs the forward and backward passes require per step (the model
family's ``train_flops_per_token``; dense: matrix parameters at 6 FLOP a
token plus causal attention), over the device time per step times chips
times the chip's bf16 peak.  The device time per step is the traced
window's busy time -- the union of the
intervals in which an op ran on a chip (bench.trace.device_busy),
averaged over the chips -- over the whole steps in that window; the
device's idle time is ``device_idle.train``'s."""
from bench import peaks, trace


def read(r):
    if not r.get("steps") or r["hi"] <= r["lo"]:
        return None
    busy = trace.device_busy(r["trace"], r["lo"], r["hi"])
    if not busy or not any(busy):
        return None
    per_step = r["family"].train_flops_per_token(r["dims"], r["seq_len"]) \
        * r["tokens_per_step"]
    t = sum(busy) / len(busy) / r["steps"]
    peak = peaks.peak(r["device_kind"])["bf16_flops"]
    return 100.0 * per_step / (t * r["chips"] * peak)
