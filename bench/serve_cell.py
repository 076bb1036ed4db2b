"""Serving cells: open-loop traffic through ``ServeEngine.step``.

Set-up makes the weights on the device in one call from the seed, builds
the engine, and runs every compiled program this mix can reach once:
each prefill (rows, length) bucket with its prompt write, each decode
occupancy bucket, and the eager slicing and greedy sampling that follow
them, at every row count.  Nothing the window needs is left to compile.

The window submits each request at the first step boundary at or after
its due time and steps the engine while it has work.  It ends at the
first step boundary after ``--seconds``.  Arrivals keep coming after
that until every request due inside the window has its first token, so
the last of them are served under the same load.

* ``serve_ttft_p50_ms``: the median over every request due in the
  window of first token time minus due time; a refused request, or one
  that never gets its first token, counts at the time the run gave up on
  it.  A median and not a tail: a window at this load holds about a
  dozen requests, too few for a tail.
* ``serve_itl_p95_ms``: over every gap between two consecutive output
  tokens of one request, both emitted inside the window.
* ``serve_tokens_per_s``: output tokens emitted inside the window over
  the window's span.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from . import common, reference, traffic as gen


def build_engine(fam, dims, model_cfg: dict, mix: dict, seed: int):
    from repro.api.spec import MeshSpec, RunSpec
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServeEngine

    mcfg = fam.program_config(dims, model_cfg)

    class CellSpec(RunSpec):
        def model_config(self):
            return mcfg

    s = mix["serve"]
    spec = CellSpec(arch=model_cfg["name"], mesh=MeshSpec(dp=1), seed=seed,
                    serve=ServeConfig(**s))
    params = fam.init_weights(dims, seed)
    return ServeEngine(spec, params=params)


def warm_up(engine, mix: dict):
    """Run each program the mix can reach once, on pad rows that write
    nothing but the null page.  The KV pool comes out of the prompt write
    and out of the decode step with different shardings, and each
    compiled program is keyed on its inputs' shardings, so the decode
    step is run from both and the prompt write from the decode's."""
    import jax
    import jax.numpy as jnp
    sc = engine.scfg
    ps = sc.page_size
    rows = gen.row_buckets(sc.max_active)
    sizes = {b: [n for n in range(1, sc.max_active + 1)
                 if engine._row_bucket(n) == b] for b in rows}
    lens = gen.prefill_buckets(mix, ps, sc.capacity)

    def z(*shape):      # host arrays moved over as the engine moves them
        return jnp.asarray(np.zeros(shape, np.int32))

    def prefill(bb, tb):
        ln = z(bb)
        logits, pkv = engine._prefill(engine.params, z(bb, tb), ln)
        engine.pool = engine._write_prompts(engine.pool, pkv,
                                            z(bb, tb // ps), ln)
        return logits

    def decode(b):
        logits, engine.pool = engine._decode(
            engine.params, engine.pool, z(b, sc.max_blocks), z(b), z(b, 1))
        return logits

    with jax.set_mesh(engine.mesh):        # as ServeEngine.step runs them
        for tb in lens:
            for bb in rows:
                decode(rows[0])
                logits = prefill(bb, tb)
                for n in sizes[bb]:
                    engine._sample(logits[:n], [None] * n)
        for b in rows:
            prefill(rows[0], lens[0])
            decode(b)
            logits = decode(b)
            for n in sizes[b]:
                engine._sample(logits[:n], [None] * n)


class Log:
    """What the window saw, on the window's own clock (seconds from t0)."""

    def __init__(self, schedule):
        self.schedule = schedule
        self.req_of: dict = {}        # engine rid -> schedule index
        self.lateness: list = []      # submit time - due time, in window
        self.refused: set = set()
        self.first: dict = {}         # schedule index -> first token time
        self.times: dict = {}         # schedule index -> token times
        self.step_s: list = []        # engine step durations, in window


def drive(engine, schedule, seconds: float, drain_s: float, span=None,
          on_window_end=None):
    """Open loop over ``schedule`` for a window of ``seconds``, then until
    every request due in the window has its first token (at most
    ``drain_s`` more).  Returns (log, window end, time of giving up)."""
    from repro.serving.scheduler import QueueFull
    log = Log(schedule)
    i, n = 0, len(schedule)
    t0 = time.perf_counter()
    t_end = None
    while True:
        now = time.perf_counter() - t0
        while i < n and schedule[i].due_s <= now:
            r = schedule[i]
            try:
                rid = engine.submit(r.prompt, r.max_new_tokens)
            except (QueueFull, ValueError):
                log.refused.add(i)
            else:
                log.req_of[rid] = i
            if t_end is None:
                log.lateness.append(now - r.due_s)
            i += 1
        if t_end is None and now >= seconds:
            t_end = now
            if on_window_end is not None:
                on_window_end()
        if t_end is not None:
            due_in = [k for k in range(i) if schedule[k].due_s < t_end]
            if all(k in log.first or k in log.refused for k in due_in) \
                    or now >= t_end + drain_s:
                break
        if engine.has_work():
            if span is not None and t_end is None:
                with span("bench.engine_step"):
                    emitted = engine.step()
            else:
                emitted = engine.step()
            t = time.perf_counter() - t0
            if t_end is None:
                log.step_s.append(t - now)
            for rid, _ in emitted:
                k = log.req_of[rid]
                log.first.setdefault(k, t)
                log.times.setdefault(k, []).append(t)
        else:
            nxt = schedule[i].due_s if i < n else now + 0.001
            time.sleep(max(0.0, min(nxt, max(seconds, now)) - now))
    return log, t_end, time.perf_counter() - t0


def window_metrics(log: Log, t_end: float, give_up: float) -> dict:
    sched = log.schedule
    due_in = [k for k in range(len(sched)) if sched[k].due_s < t_end]
    ttft = [log.first.get(k, give_up) - sched[k].due_s for k in due_in]
    itl, toks = [], 0
    for k, ts in log.times.items():
        inside = [t for t in ts if t < t_end]
        toks += len(inside)
        itl += [b - a for a, b in zip(inside, inside[1:])]
    failed = sum(1 for k in due_in if k not in log.first)
    return {
        "attempted": len(due_in), "failed": failed,
        "ttft_p50_ms": 1e3 * common.quantile(ttft, 0.50),
        "ttft_p90_ms": 1e3 * common.quantile(ttft, 0.90),
        "itl_p95_ms": 1e3 * common.quantile(itl, 0.95),
        "tokens_per_s": toks / t_end,
        "n_itl": len(itl), "n_tokens": toks,
        "lateness_ms": {
            "p50": 1e3 * common.quantile(log.lateness, 0.5),
            "p99": 1e3 * common.quantile(log.lateness, 0.99),
            "max": 1e3 * max(log.lateness, default=0.0)},
        "step_ms": {"median": 1e3 * common.quantile(log.step_s, 0.5),
                    "max": 1e3 * max(log.step_s, default=0.0)},
    }


def pick_sample(engine, log: Log, seed: int, want_tokens: int,
                max_requests: int) -> list:
    """Finished requests for the check, drawn from the seed: the longest
    (prompt + output) first, then others until ``want_tokens`` served
    tokens are in the sample."""
    done = [(log.req_of[rid], toks) for rid, toks in engine.results.items()]
    if not done:
        return []
    done.sort(key=lambda x: x[0])
    sched = log.schedule
    longest = max(done, key=lambda x: len(sched[x[0]].prompt) + len(x[1]))
    rest = [d for d in done if d is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], len(longest[1])
    for j in order:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[j])
        n += len(rest[j][1])
    return [(sched[k].prompt, list(map(int, toks))) for k, toks in out]


def ref_logits(fam, dims, w, prompt, served, seq_len: int, max_out: int,
               ar=reference.F32):
    """Reference logits at each served token's position: the model reads
    prompt + served[:-1], padded to ``seq_len`` (causal, so the padding
    is never read)."""
    import jax.numpy as jnp
    seq = list(prompt) + list(served[:-1])
    tok = np.zeros((1, seq_len), np.int32)
    tok[0, :len(seq)] = seq
    pos = len(prompt) - 1 + np.arange(len(served))
    pos_p = np.full((max_out,), pos[-1], np.int32)
    pos_p[:len(pos)] = pos
    out = fam.logits_at(dims, ar, w, jnp.asarray(tok), jnp.asarray(pos_p))
    return np.asarray(out)[:len(served)]


def check(fam, dims, mix: dict, seed: int, sample: list) -> dict:
    """The widest gap by which a served token's reference logit lies
    below the reference's best, over every position of the sample."""
    w = fam.init_weights(dims, seed)
    gap = 0.0
    seq_len = mix["serve"]["max_seq"]
    max_out = mix["output"]["max"]
    for prompt, served in sample:
        lg = ref_logits(fam, dims, w, prompt, served, seq_len, max_out)
        gap = max(gap, reference.served_gap(lg, served))
    del w
    served_n = sum(len(s) for _, s in sample)
    return {"served_gap": common.check(
        gap if sample else float("nan"), mix["limits"]["served_gap"]),
        "sampled_tokens": served_n}


def run(cell: dict, model_cfg: dict, mix: dict, seed: int, seconds: float,
        devs, counter, tracer=None) -> tuple:
    import jax

    fam = common.family(model_cfg)
    dims = fam.Dims.from_config(model_cfg)
    seed = seed % 2 ** 31
    engine = build_engine(fam, dims, model_cfg, mix, seed)
    warm_up(engine, mix)
    schedule = gen.serve_schedule(mix, seed, seconds,
                                  seconds + mix["drain_s"] + 10.0, dims.vocab)

    span = win = None
    if tracer is not None:
        tracer.wrap_serve(engine)
        span = tracer.span

    def end():
        counter.armed = False
        if tracer is not None:
            win.__exit__(None, None, None)
            tracer.stop()
    setup_s = common.process_age_s()
    counter.armed = True
    if tracer is not None:
        tracer.start()
        win = tracer.span("bench.window")   # made while the trace is on
        win.__enter__()
    log, t_end, give_up = drive(engine, schedule, seconds, mix["drain_s"],
                                span=span, on_window_end=end)
    device = common.device_info(devs)
    wm = window_metrics(log, t_end, give_up)
    sample = pick_sample(engine, log, seed, mix["check_tokens"],
                         mix["check_requests"])

    engine.params = engine.pool = None
    del engine
    gc.collect()
    for a in jax.live_arrays():
        a.delete()

    t = time.perf_counter()
    c = check(fam, dims, mix, seed, sample)
    check_s = time.perf_counter() - t
    sampled = c.pop("sampled_tokens")
    c["window_compiles"] = common.check(counter.count, 0)
    readings = {"window_s": t_end, "family": fam, "dims": dims,
                "device_kind": device["kind"], "chips": cell["chips"],
                "kv_bytes": 2, "n_layers": dims.n_layers}
    result = {
        "attempted": wm["attempted"], "failed": wm["failed"],
        "device": device,
        "e2e": {"serve_ttft_p50_ms": (wm["ttft_p50_ms"], "ms"),
                "serve_itl_p95_ms": (wm["itl_p95_ms"], "ms"),
                "serve_tokens_per_s": (wm["tokens_per_s"], "tokens/s"),
                "setup_s": (setup_s, "s")},
        "readings": readings,
        "info": {"generator_lateness_ms": wm["lateness_ms"],
                 "window_tokens": wm["n_tokens"], "itl_samples": wm["n_itl"],
                 "ttft_p90_ms": wm["ttft_p90_ms"], "step_ms": wm["step_ms"],
                 "checked_tokens": sampled, "check_s": check_s},
    }
    return result, c
