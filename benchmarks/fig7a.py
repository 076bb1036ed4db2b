"""Paper Fig. 7a: end-to-end training with the OptINC collective, with and
without Table-II error injection, vs the exact baseline.

Budgeted reproduction: the paper trains ResNet50/CIFAR-100 for 300 epochs
and LLaMA-8L/Wikipedia-1B for 50k steps on A100s; this container runs
shortened versions of BOTH models on deterministic synthetic streams and
compares final losses across sync modes. The paper's claim shape —
OptINC quantization costs almost nothing; Table-II error injection costs
slightly more but stays in range — is what we check.

The ``optinc_b2_{behavioral,mesh}`` pair puts the emulated hardware in
the loop: at bits=2 the built-in exact identity ONN resolves without
training, so ``--fidelity mesh`` runs the fast Givens-layer emulator
(repro.photonics.mesh) inside every jitted step and must reproduce the
behavioral losses EXACTLY (same RNG, bit-exact collective) — the loop
below ASSERTS that equality.  Every row runs in a CPU child
(``common.run_subprocess``); the compiled fused mesh kernel is checked
against the XLA executor by ``chip_smoke.py``'s mesh-kernel phase.

``--smoke`` (CI) runs only the short behavioral LM rows.
"""
from __future__ import annotations

import argparse
import json

from .common import emit, flush_json, run_subprocess

LM_RUN = """
import json, io, contextlib
import repro.launch.train as T
buf = io.StringIO()
with contextlib.redirect_stdout(buf):
    T.main(["--arch", "paper_llama", "--smoke-config", "--sync", "{sync}",
            "--steps", "{steps}", "--global-batch", "8", "--seq-len", "128",
            "--lr", "1e-3", "--mesh", "1x1"{extra}])
recs = [json.loads(l) for l in buf.getvalue().splitlines() if l.startswith("{{")]
last = sum(r["loss"] for r in recs[-5:]) / 5
first = sum(r["loss"] for r in recs[:5]) / 5
print(json.dumps({{"first": first, "last": last}}))
"""

RESNET_RUN = """
import json
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from repro.models import resnet
from repro.data.pipeline import synthetic_images
from repro.collectives import SyncConfig, sync_gradients
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

mesh = make_mesh((1,), ("data",))
params = resnet.init_params(jax.random.PRNGKey(0))
sync = SyncConfig(mode="{sync}", axes=("data",), bits=8, block=2048,
                  error_layers={err})

def step(params, images, labels, key):
    (l, acc), g = jax.value_and_grad(resnet.loss_fn, has_aux=True)(
        params, images, labels)
    g, _ = sync_gradients(g, sync, key, None)
    params = jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)
    return params, l, acc

sfn = jax.jit(jax.shard_map(step, mesh=mesh,
    in_specs=(P(), P("data"), P("data"), P()),
    out_specs=(P(), P(), P()), check_vma=False))
losses = []
key = jax.random.PRNGKey(1)
for s in range({steps}):
    imgs, labels = synthetic_images(s, 16)
    key, sub = jax.random.split(key)
    params, l, acc = sfn(params, jnp.asarray(imgs), jnp.asarray(labels), sub)
    losses.append(float(l))
print(json.dumps({{"first": sum(losses[:3])/3, "last": sum(losses[-3:])/3}}))
"""


def main(full: bool = False, smoke: bool = False):
    try:
        _run(full=full, smoke=smoke)
    finally:
        flush_json("fig7a")


def _run(full: bool, smoke: bool):
    lm_steps = 60 if full else (6 if smoke else 25)
    rn_steps = 30 if full else 10
    runs = [("baseline_psum", "psum", ""),
            ("optinc_ideal", "optinc", "")]
    if not smoke:
        runs += [("optinc_err3456", "optinc",
                  ', "--error-layers", "3,4,5,6"'),
                 # hardware-in-the-loop pair: bit-exact against each other
                 # (behavioral == mesh emulator; asserted below)
                 ("optinc_b2_behavioral", "optinc", ', "--bits", "2"'),
                 ("optinc_b2_mesh", "optinc",
                  ', "--bits", "2", "--fidelity", "mesh"')]
    losses = {}
    for name, sync, extra in runs:
        out = run_subprocess(LM_RUN.format(sync=sync, steps=lm_steps,
                                           extra=extra), timeout=3000)
        rec = json.loads(out.strip().splitlines()[-1])
        losses[name] = rec
        emit(f"fig7a.llama.{name}", 0.0,
             f"loss_first={rec['first']:.4f} loss_last={rec['last']:.4f} "
             f"steps={lm_steps}")
    # the advertised hardware-in-the-loop equality is a gate, not prose:
    # at bits=2 / N=1 the exact-identity ONN's analog outputs are small
    # integers represented exactly in f32, so no readout sits near a PAM4
    # decision boundary where executor rounding could flip it (the
    # trained-B=8 harness, whose readouts DO approach boundaries, budgets
    # tolerance instead — benchmarks/trained_onn.py).
    beh = losses.get("optinc_b2_behavioral")
    mesh_row = losses.get("optinc_b2_mesh")
    if beh is not None and mesh_row is not None and mesh_row != beh:
        raise RuntimeError(
            f"optinc_b2_mesh losses {mesh_row} diverged from behavioral "
            f"{beh} — the fidelity cascade is no longer bit-exact")
    if smoke:
        return
    for name, sync, err in [("baseline_psum", "psum", "()"),
                            ("optinc_err3456", "optinc", "(3,4,5,6)")]:
        out = run_subprocess(RESNET_RUN.format(sync=sync, err=err,
                                               steps=rn_steps), timeout=3000)
        rec = json.loads(out.strip().splitlines()[-1])
        emit(f"fig7a.resnet50.{name}", 0.0,
             f"loss_first={rec['first']:.4f} loss_last={rec['last']:.4f} "
             f"steps={rn_steps}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="short behavioral LM rows only (CI)")
    args = ap.parse_args()
    try:
        main(full=args.full, smoke=args.smoke)
    except RuntimeError as e:
        raise SystemExit(str(e))
