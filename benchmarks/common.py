"""Shared helpers for the benchmark harnesses."""
from __future__ import annotations

import json
import pathlib
import pickle
import subprocess
import sys
import time

RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results"
DRYRUN = RESULTS / "dryrun"     # shared with launch/dryrun.py --out and
                                # scripts/fix_dryrun_stats.py --out
BENCH_JSON = RESULTS / "bench"  # per-section JSON row dumps (CI artifacts)

_ROWS: list = []                # rows emitted since the last flush/reset


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
    _ROWS.append({"name": name, "us_per_call": round(us_per_call, 1),
                  "derived": derived})


def reset_rows():
    """Drop buffered rows (benchmarks.run calls this between sections so a
    failed section cannot leak rows into the next section's JSON)."""
    _ROWS.clear()


def flush_json(section: str) -> pathlib.Path:
    """Write (and clear) the rows emitted since the last flush to
    ``results/bench/<section>.json`` — the machine-readable mirror of the
    CSV stdout, uploaded as a CI artifact per commit."""
    BENCH_JSON.mkdir(parents=True, exist_ok=True)
    path = BENCH_JSON / f"{section}.json"
    path.write_text(json.dumps(_ROWS, indent=1) + "\n")
    _ROWS.clear()
    return path


def timed(fn, *args, repeats=3, **kw):
    fn(*args, **kw)  # warmup / compile
    t0 = time.time()
    for _ in range(repeats):
        out = fn(*args, **kw)
    return out, (time.time() - t0) / repeats * 1e6


def run_subprocess(code: str, devices: int = 0, timeout: int = 2400) -> str:
    """Run ``code`` in a child Python on the CPU and return its stdout.

    Children are always pinned to ``JAX_PLATFORMS=cpu`` (``devices`` > 0
    gives them that many host devices): the parent harness may already
    hold the accelerator, and a chip belongs to one process.  Rows built
    from children are CPU rows; on-chip checks live in ``chip_smoke.py``.
    """
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu"}
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env,
                       cwd=str(RESULTS.parent))
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-3000:])
    return r.stdout


def load_scenario1():
    # prefer the constraint-exact cayley-mode run (100% accuracy)
    for name in ("scenario1_cayley_params.pkl", "scenario1_params.pkl"):
        p = RESULTS / name
        if p.exists():
            with open(p, "rb") as f:
                return pickle.load(f)
    return None
