"""Readings of the control and of planted faults, for setting limits.

Not part of a benchmark run.  On the chip, at a cell's own size:

    python3 -m bench.controls --workload <cell> --seeds 1,2,3 [--seconds 20] \\
        [--program 1]

Training cells: on each seed the f32 reference runs the checked steps
once; the control (the reference in fp8, ``reference.FP8``) and the
half-batch fault (the reference on half the rows, the mean taken over
them) are compared with it as the program is.  A step that leaves the
state unchanged reads 1 on ``update_norm_gap`` by construction.  On
several chips the fault of the exchange left out (each chip keeps its own
gradient; chip 0's is followed) is read too.  With ``--program 1`` each
seed first runs the program as a run does (set-up, the checked steps, a
window of ``--seconds``) and prints its compared numbers: a dozen seeds'
readings in one process.

Serving cells: each seed runs a short window at the cell's load, picks
its sample as a run does, and reads both the program's served-token gap
and the control's: at each position of the same prompts and tokens, the
reference gap of the token the fp8 reference puts first.

Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np

from . import common, reference


def train_readings(model_cfg, mix, chips, seed) -> dict:
    from . import train_cell as tc
    fam = common.family(model_cfg)
    dims = fam.Dims.from_config(model_cfg)
    opt = reference.AdamW.from_config(model_cfg["optimizer"])
    rows = tc.batches(dims, mix, chips, seed)
    ex = tc.exchange_of(mix)
    ref = tc.ref_readings(fam, dims, opt, rows, seed, chips=chips, ex=ex)
    out = {}
    ctl = tc.ref_readings(fam, dims, opt, rows, seed, reference.FP8, chips,
                          ex)
    out["control_fp8"] = tc.gaps_of(*ctl, *ref)
    half = [r[: max(1, len(r) // 2)] for r in rows]
    h = tc.ref_readings(fam, dims, opt, half, seed, chips=chips, ex=ex)
    out["half_batch"] = tc.gaps_of(*h, *ref)
    if chips > 1:
        solo = tc.ref_readings(fam, dims, opt, rows, seed, chips=chips,
                               ex=reference.Exchange("none"))
        out["no_exchange"] = tc.gaps_of(*solo, *ref)
    return out


def fp8_first_gap(fam, dims, w, prompt, served, seq_len, max_out) -> float:
    from . import serve_cell as sc
    ref = sc.ref_logits(fam, dims, w, prompt, served, seq_len, max_out)
    low = sc.ref_logits(fam, dims, w, prompt, served, seq_len, max_out,
                        ar=reference.FP8)
    return reference.served_gap(ref, np.argmax(low, axis=-1))


def serve_readings(model_cfg, mix, seed, seconds, devs) -> dict:
    import jax
    from . import serve_cell as sc, traffic as gen
    fam = common.family(model_cfg)
    dims = fam.Dims.from_config(model_cfg)
    engine = sc.build_engine(fam, dims, model_cfg, mix, seed)
    sc.warm_up(engine, mix)
    schedule = gen.serve_schedule(mix, seed, seconds,
                                  seconds + mix["drain_s"] + 10, dims.vocab)
    log, t_end, _ = sc.drive(engine, schedule, seconds, mix["drain_s"])
    sample = sc.pick_sample(engine, log, seed, mix["check_tokens"],
                            mix["check_requests"])
    engine.params = engine.pool = None
    del engine
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    prog = sc.check(fam, dims, mix, seed, sample)["served_gap"]
    w = fam.init_weights(dims, seed)
    seq_len, max_out = mix["serve"]["max_seq"], mix["output"]["max"]
    gap = max(fp8_first_gap(fam, dims, w, p, s, seq_len, max_out)
              for p, s in sample)
    del w
    return {"program": prog,
            "control_fp8": {"served_gap": common.check(
                gap, mix["limits"]["served_gap"])},
            "sampled_tokens": sum(len(s) for _, s in sample)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--program", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = common.workload(args.workload)
    model_cfg = common.config_file(cell["config"])
    mix = common.traffic_file(cell["traffic"])
    sys.path.insert(0, str(common.SRC))
    devs = common.require_chips(cell["chips"])
    common.enable_compile_cache()
    counter = common.CompileCounter()
    for seed in (int(s) % 2 ** 31 for s in args.seeds.split(",")):
        if mix["kind"] == "train":
            out = {}
            if args.program:
                from . import train_cell
                res, checks = train_cell.run(cell, model_cfg, mix, seed,
                                             args.seconds, devs, counter)
                out["program"] = res["info"]["gaps"]
                out["train_tokens_per_s"] = res["e2e"][
                    "train_tokens_per_s"][0]
            out.update(train_readings(model_cfg, mix, cell["chips"], seed))
        else:
            out = serve_readings(model_cfg, mix, seed, args.seconds, devs)
        print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
