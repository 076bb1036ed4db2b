"""Run one benchmark cell once on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (configuration, traffic mix,
chips) comes from ``BENCHMARK.json``.  With ``--trace 0`` the result line
carries the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the JAX profiler and the line carries its per-layer metrics, the
device's busy and window seconds, and a breakdown.  Either way the run
ends by comparing what the timed path produced with the plain reference
(``bench.reference``), and ``correct`` says whether every compared number
is inside its limit.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when a file the cell names is missing, the model
family its configuration names among them.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import common


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = common.workload(args.workload)
        model_cfg = common.config_file(cell["config"])
        mix = common.traffic_file(cell["traffic"])
        if not (common.SRC / "repro").is_dir():
            raise common.BenchError(f"no program under {common.SRC}")
        sys.path.insert(0, str(common.SRC))
        common.family(model_cfg)
        devs = common.require_chips(cell["chips"])
    except (common.BenchError, OSError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    common.enable_compile_cache()
    measure(cell, model_cfg, mix, args.seed, args.seconds, bool(args.trace),
            devs)
    return 0


def measure(cell, model_cfg, mix, seed, seconds, trace, devs):
    """Everything of a run after the device gate."""
    from . import serve_cell, train_cell
    from .trace import Tracer
    kind = {"train": train_cell, "serve": serve_cell}[mix["kind"]]
    counter = common.CompileCounter()
    tracer = Tracer() if trace else None
    res, checks = kind.run(cell, model_cfg, mix, seed, seconds, devs,
                           counter, tracer)
    device = res["device"]
    line = {"correct": common.all_within(checks),
            "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        metrics, extra, breakdown = per_layer(cell, res["readings"], tracer)
        device.update(extra)
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = breakdown
    else:
        line["metrics"] = {
            m["name"]: {"value": res["e2e"][m["name"]][0], "unit": m["unit"]}
            for m in common.metrics_for(cell["name"], "end_to_end")}
        line["device"] = device
    if res.get("info"):
        print(json.dumps({"info": res["info"]}), flush=True)
    common.emit(line, checks)
    return line, checks


def per_layer(cell, readings, tracer):
    from . import trace as tr_mod
    tr = tracer.reduce()
    win = tr_mod.window_of(tr)
    readings = dict(readings, trace=tr, calls=tracer.calls)
    if win is None:
        readings.update(lo=0, hi=0)
        return {}, {"busy_s": 0.0, "window_s": 0.0}, {}
    lo, hi = win
    readings.update(lo=lo, hi=hi)
    busy = tr_mod.device_busy(tr, lo, hi)
    metrics = {}
    for m in common.metrics_for(cell["name"], "per_layer"):
        v = common.load_reader(m["name"])(readings)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    extra = {"busy_s": sum(busy) / max(1, len(busy)),
             "window_s": (hi - lo) * 1e-9}
    breakdown = {
        "device_ops": [[tr_mod.short_name(n), v] for n, v in
                       tr_mod.top(tr_mod.op_seconds(tr, lo, hi))],
        "idle_gaps": tr_mod.top(tr_mod.idle_by_span(tr, lo, hi)),
    }
    return metrics, extra, breakdown


if __name__ == "__main__":
    sys.exit(main())
