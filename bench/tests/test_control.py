"""The control -- the reference computed in fp8, one precision step
below the bf16 the configurations state -- put in the program's place,
at a size a test run holds: the comparison must tell it from the program.
On the chip, at the cells' own sizes, ``python3 -m bench.controls`` reads
the same numbers that set the limits (PERF.md)."""
import numpy as np

from bench import common, controls, reference, serve_cell
from bench.tests.test_faults import SEED, TINY, train


def test_fp8_control_reads_far_above_the_program():
    _, prog = train()
    mix = dict(common.traffic_file("train-stage-4k"), seq_len=16)
    ctl = controls.train_readings(TINY, mix, 1, SEED)["control_fp8"]
    ratios = [ctl[k] / max(prog[k]["value"], 1e-12) for k in prog
              if k in ctl]
    assert max(ratios) >= 3.0, (ctl, prog)


def test_fp8_first_token_gap_reads_above_served_tokens():
    dims = reference.Dims.from_config(TINY)
    w = reference.init_weights(dims, SEED)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, dims.vocab, 20).tolist()
    seq = list(prompt)
    for _ in range(12):                     # greedy from the f32 reference
        lg = serve_cell.ref_logits(dims, w, seq, [0], 64, 1)
        seq.append(int(np.argmax(lg[0])))
    served = seq[len(prompt):]
    ref = serve_cell.ref_logits(dims, w, prompt, served, 64, 12)
    assert reference.served_gap(ref, served) == 0.0
    low = controls.fp8_first_gap(dims, w, prompt, served, 64, 12)
    assert low > 0.0
