"""Knee sweep of a serving cell: the same engine under open-loop traffic
at several fixed rates, to find the highest rate it sustains without a
growing queue.  Run once, when a serving cell is defined; the cell then
fixes its rate in its traffic file.

    python3 -m bench.sweep --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.5,1,1.5,2 [--orders 7,8]

Each of ``--orders`` (default: the mix's own ``schedule_seed``) draws
the order of the mix's arrivals and lengths, as ``schedule_seed`` does
in a run.  Prints one JSON line per order and rate: TTFT p50/p90 over
the window's arrivals,
the p90 of the first and of the last third of them (a growing queue
shows as the last third's running away), ITL p95, output tokens per
second, and the queue left when the window closed.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import common


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--orders", default="")
    args = ap.parse_args(argv)
    cell = common.workload(args.workload)
    model_cfg = common.config_file(cell["config"])
    mix = common.traffic_file(cell["traffic"])
    sys.path.insert(0, str(common.SRC))
    common.require_chips(cell["chips"])
    common.enable_compile_cache()
    from . import serve_cell as sc, traffic as gen
    seed = args.seed % 2 ** 31
    fam = common.family(model_cfg)
    dims = fam.Dims.from_config(model_cfg)
    engine = sc.build_engine(fam, dims, model_cfg, mix, seed)
    sc.warm_up(engine, mix)
    orders = [int(o) for o in args.orders.split(",") if o] or \
        [mix["schedule_seed"]]
    for order, rate in ((o, float(r)) for o in orders
                        for r in args.rates.split(",")):
        sched = gen.serve_schedule(dict(mix, schedule_seed=order), seed,
                                   args.seconds, args.seconds + 120,
                                   dims.vocab, rate=rate)
        backlog = {}

        def at_end():
            backlog["queued"] = len(engine.sched.queue)
            backlog["active"] = len(engine.sched.active)
        log, t_end, give_up = sc.drive(engine, sched, args.seconds, 120.0,
                                       on_window_end=at_end)
        wm = sc.window_metrics(log, t_end, give_up)
        due = [k for k in range(len(sched)) if sched[k].due_s < t_end]
        third = max(1, len(due) // 3)
        p90 = [1e3 * common.quantile(
            [log.first.get(k, give_up) - sched[k].due_s for k in part], 0.9)
            for part in (due[:third], due[-third:])]
        while engine.has_work():            # start the next rate empty
            engine.step()
        print(json.dumps({
            "order": order, "rate_per_s": rate,
            "attempted": wm["attempted"], "failed": wm["failed"],
            "ttft_p50_ms": wm["ttft_p50_ms"], "ttft_p90_ms": wm["ttft_p90_ms"],
            "ttft_p90_first_third_ms": p90[0],
            "ttft_p90_last_third_ms": p90[1],
            "itl_p95_ms": wm["itl_p95_ms"],
            "tokens_per_s": wm["tokens_per_s"], **backlog,
            "lateness_ms": wm["lateness_ms"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
