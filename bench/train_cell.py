"""Training cells: the window drives ``TrainSession.run_step``, the
program's own step with its own host input path.

Set-up builds one session (weights, optimizer state, compiled step) and
drives it through its first steps.  Steps 0..2 are the checked steps: the
benchmark keeps what the reference needs (the weights before step 0, the
first gradient as the optimizer's first moment holds it, the weights
after step 2) and then warms up until the step time has settled.  The
window starts at a step boundary, runs whole steps, and ends at the first
step boundary after ``--seconds``, when that step's loss has been fetched.
``train_tokens_per_s`` is every token of every window step over the
window's span.

A traced run traces the window's first ``trace_steps(chips)`` whole steps
(all of them, where the window holds fewer) and reads its per-layer
metrics over those; the window itself runs as in an untraced run.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from . import common, reference

N_CHECK = 3           # steps the reference follows
# Steps times chips that a traced run captures at most.  The capture, and
# the time to write it out and read it back, grow with both; the one-chip
# cell's whole window (~130 steps) fits a run's time with room, and this
# keeps it whole while four chips capture 48 steps.
TRACE_DEVICE_STEPS = 192


def trace_steps(chips: int) -> int:
    """Whole window steps a traced run on ``chips`` chips captures."""
    return max(1, TRACE_DEVICE_STEPS // chips)


def build_session(fam, dims, model_cfg: dict, traffic: dict, chips: int,
                  seed: int):
    """A TrainSession for this cell, on the first ``chips`` devices."""
    from repro.api.session import TrainSession
    from repro.api.spec import MeshSpec, RunSpec
    from repro.collectives import SyncConfig
    from repro.data.pipeline import DataConfig
    from repro.optim import AdamWConfig

    mcfg = fam.program_config(dims, model_cfg)

    class CellSpec(RunSpec):
        def model_config(self):
            return mcfg

    opt = model_cfg["optimizer"]
    spec = CellSpec(
        arch=model_cfg["name"],
        mesh=MeshSpec(dp=chips),
        sync=SyncConfig(mode=traffic["sync"], bits=traffic["bits"],
                        block=traffic["block"], overlap=traffic["overlap"]),
        optim=AdamWConfig(**{k: opt[k] for k in (
            "lr", "b1", "b2", "eps", "weight_decay", "clip_norm")},
            moment_dtype=opt["moment_dtype"]),
        data=DataConfig(vocab=dims.vocab, seq_len=traffic["seq_len"],
                        global_batch=traffic["batch_per_chip"] * chips,
                        seed=seed, zipf_a=traffic["zipf_a"]),
        steps=2 ** 31 - 1, seed=seed)
    return TrainSession(spec, callbacks=[])


def whole_step_window(step, seconds: float, clock=time.perf_counter):
    """Call ``step(k)`` for k = 0, 1, ... from a step boundary until the
    first boundary at or after ``seconds``; each call returns only once
    its step's result is on the host.  Returns (steps, span): the rate is
    steps * work per step / span, never the steps that fit a fixed time."""
    t0 = clock()
    n = 0
    while True:
        step(n)
        n += 1
        span = clock() - t0
        if span >= seconds:
            return n, span


def run(cell: dict, model_cfg: dict, traffic: dict, seed: int,
        seconds: float, devs, counter, tracer=None) -> tuple:
    import jax

    fam = common.family(model_cfg)
    dims = fam.Dims.from_config(model_cfg)
    opt = reference.AdamW.from_config(model_cfg["optimizer"])
    chips = cell["chips"]
    seed = seed % 2 ** 31
    excluded = 0.0            # set-up seconds spent only for the check

    session = build_session(fam, dims, model_cfg, traffic, chips, seed)
    tokens_per_step = traffic["batch_per_chip"] * chips * traffic["seq_len"]
    with jax.set_mesh(session.mesh):
        t = time.perf_counter()
        p0 = jax.device_get(session.params)
        excluded += time.perf_counter() - t
        losses = []
        for step in range(N_CHECK):
            losses.append(session.run_step(step)["loss"])
            if step == 0:
                t = time.perf_counter()
                g1 = np.asarray(reference.leaf_norms(
                    session.opt_state["m"])) / (1 - opt.b1)
                excluded += time.perf_counter() - t
        t = time.perf_counter()
        p3 = jax.device_get(session.params)
        excluded += time.perf_counter() - t
        step = N_CHECK
        while step < traffic["warmup_steps"]:
            session.run_step(step)
            step += 1

        if tracer is not None:
            tracer.wrap_train(session)
        setup_s = common.process_age_s() - excluded
        counter.armed = True
        traced = trace_steps(chips) if tracer is not None else 0
        trace_end = contextlib.ExitStack()
        if tracer is not None:
            tracer.start()
            trace_end.callback(tracer.stop)
            trace_end.enter_context(tracer.span("bench.window"))
        first = step
        ends = [time.perf_counter()]
        stalls = StallLog(session.data)

        def one(k):
            with _span(tracer, "bench.run_step"):
                session.run_step(first + k)
            ends.append(time.perf_counter())
            if k + 1 == traced:
                trace_end.close()
        with stalls:
            n, window = whole_step_window(one, seconds)
        trace_end.close()
        counter.armed = False
    device = common.device_info(devs)

    # free the program's state before the reference runs
    session.params = session.opt_state = session.sync_state = None
    del session
    gc.collect()
    for a in jax.live_arrays():
        a.delete()

    readings = {
        "steps": min(n, traced) if tracer is not None else n,
        "window_s": window, "chips": chips,
        "tokens_per_step": tokens_per_step, "seq_len": traffic["seq_len"],
        "family": fam, "dims": dims, "device_kind": device["kind"],
    }
    t = time.perf_counter()
    gaps = check(fam, dims, opt, traffic, chips, seed, losses, g1, p0, p3)
    checks = compare(gaps, traffic["limits"])
    check_s = time.perf_counter() - t
    checks["window_compiles"] = common.check(counter.count, 0)
    result = {
        "attempted": n, "failed": 0, "device": device,
        "e2e": {"train_tokens_per_s": (n * tokens_per_step / window,
                                       "tokens/s"),
                "setup_s": (setup_s, "s")},
        "readings": readings,
        "info": {"gaps": gaps, "check_s": check_s,
                 "excluded_from_setup_s": excluded,
                 **stalls.summary(np.diff(ends))},
    }
    return result, checks


def exchange_of(traffic) -> "reference.Exchange":
    """The reference's counterpart of the mix's gradient sync."""
    mode = "optinc" if traffic["sync"] == "optinc" else "mean"
    return reference.Exchange(mode, traffic["bits"], traffic["block"])


def batches(dims, traffic, chips, seed) -> list:
    """The token rows of the checked steps, remade from the seed: the
    global batch, whose rows the chips take in even, contiguous parts."""
    b = traffic["batch_per_chip"] * chips
    return [reference.synthetic_tokens(dims.vocab, traffic["seq_len"], b,
                                       seed, s, traffic["zipf_a"])
            for s in range(N_CHECK)]


def ref_readings(fam, dims, opt, rows, seed, ar=reference.F32, chips=1,
                 ex=reference.MEAN) -> tuple:
    """(losses, first clipped gradient's leaf norms, leaf norms of the
    weight change) of the reference over the token ``rows`` of each
    checked step, split over ``chips`` and made one by ``ex``, from the
    seed's weights."""
    import jax
    out = reference.train(fam.loss, dims, opt, fam.init_weights(dims, seed),
                          rows, ar, chips, ex)
    w0 = fam.init_weights(dims, seed)
    d = np.asarray(reference.leaf_norms(jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"),
        out.pop("weights"), w0)))
    del w0
    gc.collect()
    return out["losses"], out["g1_norms"], d


def check(fam, dims, opt, traffic, chips, seed, losses, g1, p0,
          p3) -> dict:
    """The reference follows the checked steps on the same seed; the
    program's numbers are read against it leaf by leaf (``gaps``)."""
    ref = ref_readings(fam, dims, opt, batches(dims, traffic, chips, seed),
                       seed, chips=chips, ex=exchange_of(traffic))
    d_prog = _host_delta_norms(p0, p3)
    return gaps_of(losses, g1, d_prog, *ref)


def _host_delta_norms(p0, p3):
    """Per-leaf norm of the program's weight change, on the device."""
    import jax
    import jax.numpy as jnp
    out = []
    for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(p0)):
        d = jnp.asarray(a).astype(jnp.float32) - jnp.asarray(b).astype(
            jnp.float32)
        out.append(float(jnp.sqrt(jnp.sum(d * d))))
        del d
    return out


def leaf_gaps(prog, ref, counted=None) -> tuple:
    """Per-leaf gap of norms, |prog - ref|, over the larger of the leaf's
    reference norm and the median leaf's.  Returns (worst gap, index)."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    idx = np.arange(len(ref)) if counted is None else np.asarray(counted)
    floor = np.median(ref[idx])
    gaps = np.abs(prog[idx] - ref[idx]) / np.maximum(ref[idx], floor)
    k = int(np.argmax(gaps))
    return float(gaps[k]), int(idx[k])


def gaps_of(losses, g1, d_prog, ref_losses, ref_g1, d_ref) -> dict:
    """Three numbers:

    * ``loss_gap``: worst step's |loss - ref| / ref over the checked steps;
    * ``grad_norm_gap``: worst leaf's gap of the first clipped gradient's
      norm (the program's from its first moment after step 1);
    * ``update_norm_gap``: worst leaf's gap of the weight change's norm
      after the checked steps, over the leaves whose reference gradient
      is not nought to rounding (>= 1e-3 of the median leaf's).
    """
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    gg, _ = leaf_gaps(g1, ref_g1)
    ref_g1 = np.asarray(ref_g1)
    counted = np.nonzero(ref_g1 >= 1e-3 * np.median(ref_g1))[0]
    ug, _ = leaf_gaps(d_prog, d_ref, counted)
    return {"loss_gap": loss_gap, "grad_norm_gap": gg,
            "update_norm_gap": ug}


def compare(gaps: dict, limits: dict) -> dict:
    """Each number that the mix gives a limit, beside it.  A number with
    no limit is read and reported (``info``) but decides nothing: it
    separated no control or fault from sound runs (PERF.md)."""
    return {k: common.check(v, limits[k]) for k, v in gaps.items()
            if k in limits}


class StallLog:
    """Where a slow window's time went, on the host: each step's host
    input time (around ``data.batch``) and Python's garbage collections
    inside the window.  Reported in ``info`` with the slowest step."""

    def __init__(self, data):
        self.data, self.input_s, self.gc_s = data, [], 0.0
        self._t = self._fn = None

    def _gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc_s += time.perf_counter() - self._t

    def __enter__(self):
        fn = self._fn = self.data.batch

        def timed(*a, **kw):
            t = time.perf_counter()
            out = fn(*a, **kw)
            self.input_s.append(time.perf_counter() - t)
            return out
        self.data.batch = timed
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._gc)
        self.data.batch = self._fn

    def summary(self, step_s) -> dict:
        if not len(step_s):
            return {}
        k = int(np.argmax(step_s))
        return {"step_s": {"min": float(min(step_s)),
                           "median": common.quantile(step_s, 0.5),
                           "max": float(step_s[k])},
                "slowest_step": {"index": k, "input_s": self.input_s[k]},
                "input_s_max": max(self.input_s), "gc_s": self.gc_s}


def _span(tracer, name):
    return tracer.span(name) if tracer is not None else \
        contextlib.nullcontext()


