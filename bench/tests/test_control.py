"""The control -- the reference computed in fp8, one precision step
below the bf16 the configurations state -- put in the program's place,
at a size a test run holds: the comparison must tell it from the program.
On the chip, at the cells' own sizes, ``python3 -m bench.controls`` reads
the same numbers that set the limits (PERF.md)."""
import numpy as np

import json

from bench import common, controls, reference, serve_cell
from bench.tests.test_faults import (SEED, TINY, TINY_LIMITS, run_four_chips,
                                     train, train_mix)


def test_fp8_control_reads_far_above_the_program():
    _, prog = train()
    mix = dict(common.traffic_file("train-stage-4k"), seq_len=16)
    ctl = controls.train_readings(TINY, mix, 1, SEED)["control_fp8"]
    ratios = [ctl[k] / max(prog[k]["value"], 1e-12) for k in prog
              if k in ctl]
    assert max(ratios) >= 3.0, (ctl, prog)


def four_chip_control(traffic: str):
    """The 4-chip OptINC cell's program readings and the fp8 control's at
    the tiny size (four host CPU devices; run in a process of its own)."""
    import jax
    from bench import run
    mix = train_mix(traffic)
    cell = {"name": "train-minitron4b-optinc-4chip", "chips": 4}
    line, checks = run.measure(cell, TINY, mix, SEED, 0.2, False,
                               jax.devices()[:4])
    ctl = controls.train_readings(TINY, mix, 4, SEED)["control_fp8"]
    print(json.dumps({"correct": line["correct"], "control_fp8": ctl}))


def test_fp8_control_fails_the_four_chip_limits():
    out = run_four_chips("train-dp4-4k", "test_control", "four_chip_control")
    assert out["correct"], out
    ctl = out["control_fp8"]
    assert any(ctl[k] > limit for k, limit in TINY_LIMITS.items()), out


def test_fp8_first_token_gap_reads_above_served_tokens():
    fam = common.family(TINY)
    dims = fam.Dims.from_config(TINY)
    w = fam.init_weights(dims, SEED)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, dims.vocab, 20).tolist()
    seq = list(prompt)
    for _ in range(12):                     # greedy from the f32 reference
        lg = serve_cell.ref_logits(fam, dims, w, seq, [0], 64, 1)
        seq.append(int(np.argmax(lg[0])))
    served = seq[len(prompt):]
    ref = serve_cell.ref_logits(fam, dims, w, prompt, served, 64, 12)
    assert reference.served_gap(ref, served) == 0.0
    low = controls.fp8_first_gap(fam, dims, w, prompt, served, 64, 12)
    assert low > 0.0
