"""Model families found by name: the configurations' family resolves, an
unknown one stops a run before the chip gate, a family added as files
alone runs both kinds of cell, and a traced training run captures the
steps its bound allows."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import common, run, train_cell
from bench.tests.test_faults import SEED, TINY, serve_mix, train_mix

CONFIGS = [c["name"] for c in common.benchmark()["configs"]]


def subprocess_env(root, devices: int = 1) -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
                PYTHONPATH=os.pathsep.join([str(common.SRC), str(root)]))


@pytest.mark.parametrize("name", CONFIGS)
def test_configs_name_the_dense_family(name):
    cfg = common.config_file(name)
    fam = common.family(cfg)
    assert cfg["family"] == "dense" and fam.__name__ == "bench.families.dense"
    assert common.family(cfg) is fam
    dims = fam.Dims.from_config(cfg)
    assert fam.program_config(dims, cfg).family == "dense"


@pytest.mark.parametrize("family", ["nosuch", None, "../reference"])
def test_unknown_family_exits_2(family, monkeypatch, capsys):
    cfg = dict(common.config_file("minitron-4b-stage4"), family=family)
    if family is None:
        del cfg["family"]
    monkeypatch.setattr(common, "config_file", lambda name: cfg)
    rc = run.main(["--workload", "train-minitron4b-1chip", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "no known model family" in out.err and repr(family) in out.err


NEW_FAMILY_RUN = """
import json, jax
from bench import common, run
out = {}
for name in ("tiny-train", "tiny-chat"):
    cell = common.workload(name)
    cfg = common.config_file(cell["config"])
    mix = common.traffic_file(cell["traffic"])
    out["family_file"] = common.family(cfg).__file__
    line, checks = run.measure(cell, cfg, mix, %d, 0.3, False,
                               jax.devices()[:1])
    out[name] = {"correct": line["correct"], "checks": checks,
                 "metrics": sorted(line["metrics"])}
print(json.dumps(out))
"""


def test_new_family_runs_from_added_files_alone(tmp_path):
    """A copy of the benchmark to which only files are added -- a family
    (``dense.py`` under a new name), a configuration naming it, two
    traffic mixes -- and entries in ``BENCHMARK.json`` runs a training and
    a serving cell of the new family on the CPU, correct."""
    shutil.copytree(common.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(common.BENCH_DIR / "families" / "dense.py",
                tmp_path / "bench" / "families" / "densecopy.py")
    cfg = dict(TINY, name="tiny-densecopy", family="densecopy")
    (tmp_path / "bench" / "configs" / "tiny-densecopy.json").write_text(
        json.dumps(cfg))
    for name, mix in (("tiny-train", train_mix()), ("tiny-chat", serve_mix())):
        (tmp_path / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench = common.benchmark()
    bench["configs"].append({
        "name": "tiny-densecopy", "source": "https://example.org/tiny",
        "file": "bench/configs/tiny-densecopy.json", "reduced": [],
        "why": "a new family"})
    for name in ("tiny-train", "tiny-chat"):
        bench["workloads"].append({"name": name, "config": "tiny-densecopy",
                                   "traffic": name, "chips": 1, "why": "-"})
        for m in bench["end_to_end"]:
            kind = "train" if name == "tiny-train" else "serve"
            if "workloads" in m and m["name"].startswith(kind):
                m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    p = subprocess.run([sys.executable, "-c", NEW_FAMILY_RUN % SEED],
                       cwd=tmp_path, env=subprocess_env(tmp_path),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["family_file"] == str(
        tmp_path / "bench" / "families" / "densecopy.py")
    assert out["tiny-train"]["correct"], out
    assert out["tiny-train"]["metrics"] == ["setup_s", "train_tokens_per_s"]
    assert out["tiny-chat"]["correct"], out
    assert "serve_itl_p95_ms" in out["tiny-chat"]["metrics"]


@pytest.mark.parametrize("chips,steps", [(1, 192), (4, 48)])
def test_trace_bound_from_chips(chips, steps):
    assert train_cell.trace_steps(chips) == steps


def traced_four_chips():
    """The 4-chip OptINC cell at the tiny size on four host CPU devices,
    traced, with the bound cut to 2 steps so that a 1.5 s window runs
    past it.  Prints the window's steps, the readings' steps, and the
    ``bench.run_step`` spans the capture holds inside ``bench.window``."""
    import jax
    from bench import trace
    train_cell.TRACE_DEVICE_STEPS = 8
    tracer = trace.Tracer()
    cell = {"name": "train-minitron4b-optinc-4chip", "chips": 4}
    res, checks = train_cell.run(cell, TINY, train_mix("train-dp4-4k"), SEED,
                                 1.5, jax.devices()[:4],
                                 common.CompileCounter(), tracer)
    tr = tracer.reduce()
    lo, hi = trace.window_of(tr)
    spans = [s for s in tr["spans"] if s[0] == "bench.run_step"]
    print(json.dumps({"window_steps": res["attempted"],
                      "steps": res["readings"]["steps"],
                      "spans_in_window": sum(lo <= s <= e <= hi
                                             for _, s, e in spans),
                      "spans": len(spans),
                      "correct": common.all_within(checks)}))


def test_traced_run_captures_its_bound():
    p = subprocess.run(
        [sys.executable, "-c", "from bench.tests.test_families import "
         "traced_four_chips; traced_four_chips()"],
        cwd=common.ROOT, env=subprocess_env(common.ROOT, 4),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["window_steps"] > 2, out
    assert out["steps"] == out["spans_in_window"] == out["spans"] == 2, out
    assert out["correct"], out
