"""Shared plumbing of the on-chip benchmark: file lookup by name, the
device gate, the compile cache, set-up timing, statistics and the result
line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own and is found here by the name
that ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  model configuration as it is run
* ``bench/traffic/<traffic>.json`` traffic mix (parameters only)
* ``bench/metrics/<metric>.py``    per-layer metric reader
* ``bench/families/<family>.py``  model family, named by the config's
  ``"family"``
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import pathlib
import re
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, missing file, ...)."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def config_file(name: str) -> dict:
    for c in benchmark()["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise BenchError(f"no config {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def metrics_for(cell: str, section: str) -> list:
    """The ``section`` ('end_to_end' or 'per_layer') metrics that ``cell``
    reports: those listing it under ``workloads``, or, without that key,
    those of every cell that reports the metric they move."""
    bench = benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def reports(m):
        if "workloads" in m:
            return cell in m["workloads"]
        if section == "per_layer":
            return reports(e2e[m["moves"]])
        return True
    return [m for m in bench[section] if reports(m)]


def load_reader(metric: str):
    """The ``read(readings)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(model_cfg: dict):
    """The module ``bench/families/<family>.py`` of the family that the
    configuration names, loaded once per process (its jitted functions
    and ``Dims`` class stay one object)."""
    name = model_cfg.get("family")
    path = BENCH_DIR / "families" / f"{name}.py"
    if not (isinstance(name, str) and path.is_file()
            and re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_-]*", name)):
        known = sorted(p.stem for p in path.parent.glob("[!_]*.py"))
        raise BenchError(f"configuration {model_cfg.get('name')!r} names "
                         f"no known model family ({name!r}; known: {known})")
    mod_name = f"bench.families.{name}"
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    return sys.modules[mod_name]


def process_age_s() -> float:
    """Seconds since this process started (kernel clock, 10 ms steps)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start


def enable_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache key), unless the environment
    names one.  Every program is cached, however quick its compile, so
    that only the first run of a cell in a checkout compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    """The accelerator gate: TPU devices, at least ``n`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts JAX traces, lowerings and compiles (cache loads included)
    while armed; a window that sees any has compiled inside it."""

    def __init__(self):
        from jax import monitoring
        self.armed = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **kw):
        if self.armed and name.startswith("/jax/core/compile/"):
            self.count += 1


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation between order
    statistics (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def emit(result: dict, checks: dict):
    """Print each compared number beside its limit as the last lines of
    stderr, then the result line, with ``checks`` as its last key, as the
    last line of stdout."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def check(value, limit) -> dict:
    return {"value": value, "limit": limit}


def all_within(checks: dict) -> bool:
    return all(isinstance(c["value"], (int, float))
               and math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
