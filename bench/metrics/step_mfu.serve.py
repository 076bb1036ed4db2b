"""Whole-step model FLOP/s utilization of a serving window, in %.

FLOPs the forward passes require (the model family's ``prefill_flops``
and ``decode_flops``: each admitted prompt at its real length, each
decode token over its real context) summed over the prefill and decode
calls in the traced window, over the window's device-trace span times
chips times the chip's bf16 peak."""
from bench import peaks


def read(r):
    pre = r["calls"].get("bench.prefill") or []
    dec = r["calls"].get("bench.decode") or []
    if not (pre or dec) or r["hi"] <= r["lo"]:
        return None
    f, d = r["family"], r["dims"]
    total = sum(f.prefill_flops(d, n) for rows in pre for n in rows)
    total += sum(f.decode_flops(d, n + 1) for rows in dec for n in rows)
    span = (r["hi"] - r["lo"]) * 1e-9
    peak = peaks.peak(r["device_kind"])["bf16_flops"]
    return 100.0 * total / (span * r["chips"] * peak)
