"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them into a schedule.  A new mix is
a new data file, never new code.

Serving mixes are open-loop: each request has a due time, fixed before
the run, whatever the server does.  Gaps between due times are the
quantiles of an exponential distribution (Poisson arrivals at ``rate``),
scaled so that the window holds rate x seconds requests; prompt and
output lengths are the quantiles of lognormal distributions clipped to
their bounds.  Their order is drawn once from the mix's own
``schedule_seed``, so every run replays the same arrivals and lengths: a
window holds about a dozen requests, too few for a seed-drawn order to
leave its tails steady.  The run's seed draws the prompt tokens (and the
weights), which change no amount of work: decoding is greedy to a fixed
budget.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float
    prompt: list
    max_new_tokens: int


def _norm_ppf(p):
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    lo = 0.02425
    if p < lo:
        q = math.sqrt(-2 * math.log(p))
        return (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    if p > 1 - lo:
        return -_norm_ppf(1 - p)
    q = p - 0.5
    r = q * q
    return (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
            + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r
                            + b[4]) * r + 1)


def lognormal_lengths(n: int, dist: dict) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of a lognormal with the
    given median and sigma, rounded and clipped to [min, max]."""
    z = np.array([_norm_ppf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def poisson_gaps(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps at the quantiles (i + 0.5) / n of an
    exponential distribution of mean 1 / rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _block(mix, order, rng, t0: float, span: float, rate: float,
           vocab: int):
    """round(rate * span) requests due in [t0, t0 + span): the gaps are
    the exponential quantiles scaled to fill the span exactly, the
    lengths the lognormal quantiles, both in the order ``order`` draws;
    prompt tokens from ``rng``."""
    n = max(1, int(round(rate * span)))
    gaps = poisson_gaps(n, rate)
    gaps = order.permutation(gaps * (span / gaps.sum()))
    prompts = order.permutation(lognormal_lengths(n, mix["prompt"]))
    outputs = order.permutation(lognormal_lengths(n, mix["output"]))
    due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Request(float(t), rng.integers(0, vocab, size=int(p)).tolist(),
                    int(o)) for t, p, o in zip(due, prompts, outputs)]


def serve_schedule(mix: dict, seed: int, seconds: float, horizon_s: float,
                   vocab: int, rate: float | None = None) -> list:
    """Requests due in [0, horizon_s), sorted by due time: a block that
    fills the window [0, seconds) and one that keeps the load on after
    it.  Prompt tokens are drawn from ``seed``, uniformly in [0, vocab)."""
    rate = mix["rate_per_s"] if rate is None else rate
    order = np.random.default_rng(mix["schedule_seed"])
    rng = np.random.default_rng(seed)
    out = _block(mix, order, rng, 0.0, seconds, rate, vocab)
    if horizon_s > seconds:
        out += _block(mix, order, rng, seconds, horizon_s - seconds, rate,
                      vocab)
    return out


def prefill_buckets(mix: dict, page_size: int, capacity: int) -> list:
    """The prompt-length buckets a mix can reach: the engine pads a
    prompt to the next power of two, page-aligned, capped at capacity."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out = set()
    for t in (lo, hi, *[1 << k for k in range(1, 20)]):
        if lo <= t <= hi:
            tb = -(-max(page_size, 1 << (t - 1).bit_length()) // page_size)
            out.add(min(tb * page_size, capacity))
    return sorted(out)


def row_buckets(max_active: int) -> list:
    """Power-of-two row counts up to ``max_active``."""
    out, b = [], 1
    while b < max_active:
        out.append(b)
        b *= 2
    return out + [max_active]
