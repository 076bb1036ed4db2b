"""Drive the OptINC system's main path once on a TPU and check its outputs.

    python chip_smoke.py              # one chip: train, serve, mesh kernel
    python chip_smoke.py --chips 4    # four chips: psum vs optinc vs cascade

One process holds the chip(s) and goes through the repo's own entry points
(``RunSpec`` -> ``TrainSession`` -> ``ServeSession.engine``) with
paper_llama at its full published config (8 layers, d 384, 8 heads, vocab
32000) and random weights made from ``--seed``.

One chip runs three phases in order:

* train: ``--sync optinc --bits 8 --mesh 1x1``, global batch 32 x 512, 6
  steps; every loss finite and the mean of the last 3 below the first 3;
* serve: the trained params behind the paged engine (page 16, 8 slots,
  ``max_seq`` 512), 8 requests of 17-300 prompt tokens, 32 new tokens
  each.  The compiled decode step must hold the Pallas kernel
  (``tpu_custom_call``); on the engine's own pool the kernel must match
  ``decode_attention`` over ``paged_gather`` within 1e-5; the same
  requests rerun on the gather backend may differ only after a near-tie
  (top-2 logit gap <= 1e-3);
* mesh kernel: ``mesh_scan_blocks`` (the compiled ``--mesh-backend
  pallas`` executor) against the XLA executor on random rotation programs
  at the scenario-1 ONN widths (64-256 wires), B = 4, batch 4096: within
  1e-5 without phase noise, and identical draws for the same key with it.

``--chips 4`` runs only the cross-chip gradient collective: paper_llama
with the same seed and global batch under ``psum`` (4x1, the exact
baseline), ``optinc`` (4x1) and ``cascade`` (2x1 x 2 pods).  Step-0
losses must be identical; later ones may stray from psum's by at most
``COLLECTIVE_TOL`` of psum's loss drop so far; every device must hold
live buffers.

Lines before the last are informational.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.api import RunSpec, ServeSession, TrainSession  # noqa: E402
from repro.api.callbacks import Callback, default_callbacks  # noqa: E402
from repro.kernels.paged_attention import paged_attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.layers import (ShardCtx, decode_attention,  # noqa: E402
                                 paged_gather)
from repro.photonics import mzi  # noqa: E402
from repro.photonics.mesh import compile_layer  # noqa: E402
from repro.photonics.pipeline import PhaseNoise  # noqa: E402

MODEL = ["--arch", "paper_llama", "--global-batch", "32", "--seq-len", "512"]
SERVE = ["--decode-backend", "paged", "--page-size", "16",
         "--max-active", "8", "--max-seq", "512"]
TRAIN_STEPS = 6
N_REQUESTS, NEW_TOKENS = 8, 32
KERNEL_TOL = 1e-5        # paged kernel vs gather oracle, f32 accumulation
NEAR_TIE = 1e-3          # top-2 logit gap below which argmax may flip
MESH_WIDTHS, MESH_BLOCKS, MESH_BATCH = (64, 128, 256), 4, 4096
MESH_TOL, THETA_STD = 1e-5, 0.02
COLLECTIVE_STEPS = 4
# B-bit gradient codes may cost a share of psum's progress, not more:
# |loss - psum loss| <= COLLECTIVE_TOL * (psum step-0 loss - psum loss)
COLLECTIVE_TOL = 0.15


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


def device_gate(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devices[0].platform!r}. No phase was run.")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices; JAX found {len(devices)}")
    return devices


# ---------------------------------------------------------------- train

class StepClock(Callback):
    """Per-step wall seconds, each step ended by ``block_until_ready`` on
    the updated state (the first step includes its compile)."""

    def on_train_start(self, session):
        self.seconds = []
        self._t = time.perf_counter()

    def on_step(self, session, record):
        jax.block_until_ready((session.params, session.opt_state))
        now = time.perf_counter()
        self.seconds.append(now - self._t)
        self._t = now


def train(argv: list, steps: int, seed: int):
    """One ``TrainSession`` run of ``MODEL + argv``; returns the session
    and its reported losses, and logs compile and step seconds."""
    spec = RunSpec.from_args(MODEL + argv + ["--steps", str(steps),
                                             "--seed", str(seed)])
    clock = StepClock()
    session = TrainSession(spec, callbacks=default_callbacks(spec) + [clock])
    losses = [r["loss"] for r in session.run()]
    check(len(losses) == steps, f"{argv}: {len(losses)} of {steps} steps ran")
    check(all(math.isfinite(x) for x in losses),
          f"{argv}: non-finite loss {losses}")
    steady = statistics.median(clock.seconds[1:])
    log(f"train {' '.join(argv)}: first step (compile + run) "
        f"{clock.seconds[0]:.3f} s, compile ~{clock.seconds[0] - steady:.3f} "
        f"s, steady step {steady:.4f} s")
    return session, losses


def train_phase(seed: int):
    session, losses = train(["--sync", "optinc", "--bits", "8",
                             "--mesh", "1x1"] + SERVE, TRAIN_STEPS, seed)
    first, last = np.mean(losses[:3]), np.mean(losses[-3:])
    check(last < first, f"loss did not fall: first 3 mean {first}, last 3 "
                        f"mean {last} ({losses})")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"train: losses {losses}; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use')}")
    log("phase train: passed")
    return session


# ---------------------------------------------------------------- serve

def make_prompts(seed: int, vocab: int) -> list:
    """N_REQUESTS prompts of 17-300 tokens, none a whole number of pages."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(17, 301, size=N_REQUESTS)
    lens = np.where(lens % 16 == 0, lens + 1, lens)
    return [rng.integers(0, vocab, size=int(n)).tolist() for n in lens]


def slot_batch(engine):
    """(page_table, lengths) of the engine's active slots, lengths
    counting the positions already written to the pool."""
    act = engine.sched.active
    pt = np.zeros((len(act), engine.scfg.max_blocks), np.int32)
    ln = np.zeros((len(act),), np.int32)
    for i, seq in enumerate(act):
        pt[i, :len(seq.pages)] = seq.pages
        ln[i] = seq.length
    return jnp.asarray(pt), jnp.asarray(ln)


def kernel_vs_gather(engine, seed: int) -> float:
    """Worst |paged kernel - decode_attention over paged_gather| over every
    layer of the engine's pool, for one random f32 query per slot."""
    cfg = engine.cfg
    pt, ln = slot_batch(engine)
    k_all, v_all = (engine.pool["layers"][n] for n in ("k", "v"))
    h = k_all.shape[2] * (cfg.n_heads // cfg.n_kv_heads)
    q = jax.random.normal(jax.random.PRNGKey(seed), (pt.shape[0], h, 1,
                                                     cfg.hd), jnp.float32)
    kernel = jax.jit(paged_attention)

    @jax.jit
    def oracle(q, k, v, pt, ln):
        return decode_attention(ShardCtx(), q, paged_gather(k, pt),
                                paged_gather(v, pt), ln)

    worst = 0.0
    for layer in range(cfg.n_layers):
        got = kernel(q, k_all[layer], v_all[layer], pt, ln)
        want = oracle(q, k_all[layer], v_all[layer], pt, ln)
        worst = max(worst, float(jnp.max(jnp.abs(got - want))))
    return worst


def decode_hlo(engine) -> str:
    """Compiled HLO of the engine's jitted decode step at a full slot
    bucket."""
    b, nb = engine.scfg.max_active, engine.scfg.max_blocks
    with jax.set_mesh(engine.mesh):
        return engine._decode.lower(
            engine.params, engine.pool, jnp.zeros((b, nb), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, 1), jnp.int32)).compile().as_text()


def drain(engine, prompts, on_step=None):
    """Submit every prompt, step the engine dry; returns the generated
    tokens in submission order and the seconds spent in ``step``."""
    rids = [engine.submit(p, NEW_TOKENS) for p in prompts]
    spent = 0.0
    while engine.has_work():
        t0 = time.perf_counter()
        engine.step()
        spent += time.perf_counter() - t0
        if on_step is not None:
            on_step(engine)
    return [engine.results[r] for r in rids], spent


def first_divergences(sess, prompts, got, want) -> list:
    """(request, token index, paged token, gather token, top-2 logit gap
    of the model at that point) for each request whose two runs differ."""
    vocab = sess.cfg.vocab
    out = []
    for i, (g, w) in enumerate(zip(got, want)):
        diff = [j for j, (a, b) in enumerate(zip(g, w)) if a != b]
        if diff:
            j = diff[0]
            logits, _ = sess.prefill(np.asarray([prompts[i] + w[:j]],
                                                np.int32))
            top2 = np.sort(np.asarray(logits[0, :vocab], np.float64))[-2:]
            out.append((i, j, g[j], w[j], float(top2[1] - top2[0])))
    return out


def serve_phase(session, seed: int):
    spec, params = session.spec, session.params
    prompts = make_prompts(seed, session.cfg.vocab)
    log(f"serve: prompt lengths {[len(p) for p in prompts]}")

    errors = []

    def probe(engine):
        # once every request decodes, check the kernel on the live pool
        if not errors and engine.step_count >= 3 and (
                len(engine.sched.active) == N_REQUESTS):
            errors.append(kernel_vs_gather(engine, seed))

    paged = ServeSession(spec, params=params).engine()
    got, spent = drain(paged, prompts, probe)
    log(f"serve paged: {paged.step_count} engine steps in {spent:.3f} s "
        f"(first step includes compiles)")
    has_kernel = "tpu_custom_call" in decode_hlo(paged)
    log(f"serve: compiled decode step holds the Pallas kernel "
        f"(tpu_custom_call): {has_kernel}")
    if errors:
        log(f"serve: paged kernel vs gather oracle on the engine's pool, "
            f"max abs error {errors[0]:.3e} (bound {KERNEL_TOL})")

    gspec = dataclasses.replace(
        spec, serve=dataclasses.replace(spec.serve, decode_backend="gather"))
    want, _ = drain(ServeSession(gspec, params=params).engine(), prompts)
    agree = sum(int(a == b) for g, w in zip(got, want) for a, b in zip(g, w))
    log(f"serve: paged vs gather token agreement {agree}/"
        f"{N_REQUESTS * NEW_TOKENS}")
    diverged = first_divergences(ServeSession(spec, params=params), prompts,
                                 got, want)
    for i, j, a, b, gap in diverged:
        log(f"serve: request {i} first differs at token {j} (paged {a}, "
            f"gather {b}), top-2 logit gap {gap:.3e}")

    check(all(len(t) == NEW_TOKENS for t in got + want),
          f"generated {[len(t) for t in got]} (paged), "
          f"{[len(t) for t in want]} (gather) tokens")
    check(has_kernel, "the compiled decode step holds no tpu_custom_call: "
                      "the paged kernel is not in it")
    check(bool(errors), "the kernel check never saw all requests decoding")
    check(errors[0] <= KERNEL_TOL,
          f"paged kernel error {errors[0]:.3e} > {KERNEL_TOL}")
    check(all(gap <= NEAR_TIE for *_, gap in diverged),
          f"paged and gather diverged away from a near-tie: {diverged}")
    log("phase serve: passed")


# ---------------------------------------------------------- mesh kernel

def random_layer(rng, s: int, tall: bool):
    """An ``approx`` ONN layer of MESH_BLOCKS random s-wire rotation
    programs: tall (B*s, s) layers share the input across blocks, wide
    (s, B*s) layers give each block its own slice."""
    blocks = []
    for _ in range(MESH_BLOCKS):
        q, _ = np.linalg.qr(rng.normal(size=(s, s)))
        blocks.append({"u": mzi.givens_decompose(q),
                       "d": rng.normal(size=s)})
    n = MESH_BLOCKS * s
    return compile_layer({"kind": "approx", "blocks": blocks,
                          "shape": (n, s) if tall else (s, n),
                          "b": np.zeros(n if tall else s)}, jnp.float32)


def warm_time(fn, *args):
    """(result, seconds of one call after a warm-up call)."""
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    out = fn(*args).block_until_ready()
    return out, time.perf_counter() - t0


def mesh_phase(seed: int):
    rng = np.random.default_rng(seed)
    noise = PhaseNoise(theta_drift_std=THETA_STD)
    for s in MESH_WIDTHS:
        for tall in (True, False):
            layer = random_layer(rng, s, tall)
            x = jnp.asarray(rng.normal(size=(MESH_BATCH, layer.shape[1])),
                            jnp.float32)
            clean = jax.jit(lambda x: layer.apply(x, backend="pallas"))
            drifted = jax.jit(lambda x, k: layer.apply(
                x, backend="pallas", noise=noise, key=k))
            xla = jax.jit(lambda x: layer.apply(x, backend="xla"))
            got, t_pallas = warm_time(clean, x)
            want, t_xla = warm_time(xla, x)
            err = float(jnp.max(jnp.abs(got - want)))
            key = jax.random.PRNGKey(seed + s)
            a, b = np.asarray(drifted(x, key)), np.asarray(drifted(x, key))
            c = np.asarray(drifted(x, jax.random.fold_in(key, 1)))
            drift = float(np.max(np.abs(a - np.asarray(got))))
            log(f"mesh {layer.shape} depth {layer.meshes.depth}: pallas vs "
                f"xla max abs error {err:.3e}; one apply: pallas "
                f"{t_pallas:.5f} s, xla {t_xla:.5f} s; theta drift "
                f"{THETA_STD} moves outputs by up to {drift:.3e}")
            check(err <= MESH_TOL, f"mesh {layer.shape}: pallas vs xla "
                                   f"error {err:.3e} > {MESH_TOL}")
            check(np.array_equal(a, b), f"mesh {layer.shape}: theta drift "
                                        f"differs for the same key")
            check(drift > 0.0 and not np.array_equal(a, c),
                  f"mesh {layer.shape}: theta drift does not depend on the "
                  f"key")
    log("phase mesh kernel: passed")


# ----------------------------------------------------- four-chip collective

COLLECTIVE_RUNS = {
    "psum": ["--sync", "psum", "--mesh", "4x1"],
    "optinc": ["--sync", "optinc", "--bits", "8", "--mesh", "4x1"],
    "cascade": ["--sync", "cascade", "--bits", "8", "--mesh", "2x1",
                "--pods", "2"],
}


def collective_phase(seed: int):
    sessions, losses = {}, {}
    for name, argv in COLLECTIVE_RUNS.items():
        sessions[name], losses[name] = train(argv, COLLECTIVE_STEPS, seed)
        log(f"collective {name}: losses {losses[name]}")
    base = losses["psum"]
    check(len({runs[0] for runs in losses.values()}) == 1,
          f"step-0 losses differ: { {k: v[0] for k, v in losses.items()} }")
    bound = [COLLECTIVE_TOL * (base[0] - b) for b in base]
    log(f"collective: tolerance |loss - psum| <= {COLLECTIVE_TOL} x psum's "
        f"loss drop = {[round(b, 5) for b in bound]}")
    for name in ("optinc", "cascade"):
        delta = [a - b for a, b in zip(losses[name], base)]
        log(f"collective {name} - psum per step: "
            f"{[round(d, 5) for d in delta]}")
        check(all(abs(d) <= t for d, t in zip(delta, bound)),
              f"{name} strays from psum: {delta} vs bound {bound}")
    log(f"collective: cascade losses equal optinc's: "
        f"{losses['cascade'] == losses['optinc']}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in jax.devices()[:4]]
    log(f"collective: bytes_in_use per device {in_use}")
    check(all(b > 0 for b in in_use), f"a device holds nothing: {in_use}")
    log("phase collective: passed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, serve and mesh-kernel phases; 4: only "
                         "the cross-chip collective comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, data and requests")
    args = ap.parse_args(argv)
    devices = device_gate(args.chips)
    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.chips == 4:
        phases = [lambda: collective_phase(args.seed)]
    else:
        phases = [lambda: serve_phase(train_phase(args.seed), args.seed),
                  lambda: mesh_phase(args.seed)]
    failed = []
    for phase in phases:
        # a failed phase fails the run; the next phase still runs, so one
        # chip call reports every phase
        try:
            phase()
        except SmokeFailure as e:
            failed.append(str(e))
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
