"""The program's spans and scopes in a capture (bench.phases): the phase
rule, the readings on small neutral-form traces, a recorded v5e capture,
and captures of the program's own training and serving steps."""
import json
import pathlib

import pytest

from bench import common, phases, trace

DATA = pathlib.Path(__file__).parent / "data"


@pytest.mark.parametrize("path,phase", [
    ("jit(step)/jvp(forward)/while/body/closed_call/dot_general", "forward"),
    ("jit(step)/forward/dot_general", "forward"),
    ("jit(step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/"
     "rematted_computation/mul", "backward"),
    ("jit(step)/grad_sync/concatenate", "grad_sync"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(step)/grad_sync/optimizer/transpose(jvp(forward))/add",
     "grad_sync"),
    ("jit(step)/optimizer/transpose(jvp(forward))/add", "optimizer"),
    ("jit(step)/jit(clip)", None),
    ("jit(step)/forwarded/add", None),
    ("", None),
])
def test_phase_rule(path, phase):
    assert phases.phase_of(path) == phase


# two devices, times in ns; the window is [0, 100] and holds two steps
SCOPES = {"fwd": "jit(step)/jvp(forward)/dot_general",
          "bwd": "jit(step)/transpose(jvp(forward))/dot_general",
          "sync": "jit(step)/grad_sync/concatenate",
          "opt": "jit(step)/optimizer/add",
          "key": "jit(_threefry_fold_in)/concatenate"}
PROGRAM = [("train.step", 0, 50, {"step": 0}), ("train.input", 0, 10, {}),
           ("train.step", 50, 100, {"step": 1}),
           ("train.input", 50, 60, {})]


def two_devices():
    dev0 = [("fwd", 10, 20), ("bwd", 20, 35), ("sync", 35, 38),
            ("opt", 38, 45), ("key", 45, 46),
            ("fwd", 62, 72), ("bwd", 72, 87), ("sync", 87, 90),
            ("opt", 90, 97)]
    dev1 = [("fwd", 12, 22), ("bwd", 22, 37), ("sync", 37, 38),
            ("opt", 38, 47),
            ("fwd", 60, 70), ("bwd", 70, 85), ("sync", 85, 88),
            ("opt", 88, 95)]
    return {
        "devices": {"/device:TPU:0": {"ops": dev0, "modules": []},
                    "/device:TPU:1": {"ops": dev1, "modules": []}},
        "spans": [("bench.window", 0, 100)] +
                 [(n, s, e) for n, s, e, _ in PROGRAM],
        "program_spans": PROGRAM, "scopes": SCOPES}


def test_phase_time_is_averaged_over_devices():
    per = phases.phase_seconds(two_devices(), 0, 100)
    assert per["forward"] == pytest.approx(20e-9)
    assert per["backward"] == pytest.approx(30e-9)
    assert per["grad_sync"] == pytest.approx((6 + 4) / 2 * 1e-9)
    assert per["optimizer"] == pytest.approx((14 + 16) / 2 * 1e-9)
    assert per["unscoped"] == pytest.approx(0.5e-9)


def test_readings_are_per_window_step():
    tr = two_devices()
    r = {"trace": tr, "lo": 0, "hi": 100, "steps": 2}
    got = phases.readings(r)
    assert got["forward_ms.train"] == pytest.approx(1e3 * 20e-9 / 2)
    assert got["backward_ms.train"] == pytest.approx(1e3 * 30e-9 / 2)
    assert got["optimizer_ms.train"] == pytest.approx(1e3 * 15e-9 / 2)
    assert got["grad_sync_ms.train"] == pytest.approx(1e3 * 5e-9 / 2)
    busy = trace.device_busy(tr, 0, 100)
    assert got["busy_ms.train"] == pytest.approx(1e3 * sum(busy) / 2 / 2)
    # both devices idle through both train.input spans, [0, 10], [50, 60]
    assert phases.idle_in(tr, "train.input", 0, 100) == \
        (pytest.approx(20e-9), 2)
    assert got["input_idle_ms.train"] == pytest.approx(1e3 * 20e-9 / 2)


def test_host_gap_is_per_engine_step():
    ops = [("decode", 10, 40), ("decode", 60, 95)]
    steps = [("serve.step", 5, 45, {"active": 2, "queued": 0,
                                    "preempted": 0}),
             ("serve.step", 50, 98, {"active": 2, "queued": 1,
                                     "preempted": 0}),
             ("serve.step", 120, 130, {"active": 0, "queued": 0,
                                       "preempted": 0})]
    tr = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
          "spans": [(n, s, e) for n, s, e, _ in steps],
          "program_spans": steps, "scopes": {}}
    got = phases.readings({"trace": tr, "lo": 0, "hi": 100})
    # idle inside the two steps that start in the window: 5 + 5, 10 + 3
    assert got["host_gap_ms.serve"] == pytest.approx(1e3 * 23e-9 / 2)
    assert got["engine_steps.serve"] == 2


def test_nothing_to_read_reads_none():
    """A capture of a program without spans or scopes (the parent's)."""
    tr = two_devices()
    bare = dict(tr, spans=[("bench.window", 0, 100)], program_spans=[],
                scopes={})
    got = phases.readings({"trace": bare, "lo": 0, "hi": 100, "steps": 2})
    assert got.pop("busy_ms.train") > 0
    assert set(got.values()) == {None}
    old_form = {"devices": tr["devices"], "spans": bare["spans"]}
    got = phases.readings({"trace": old_form, "lo": 0, "hi": 100,
                           "steps": 2})
    assert got.pop("busy_ms.train") > 0 and set(got.values()) == {None}
    assert phases.readings({"trace": old_form, "lo": 0, "hi": 100}) == {
        "host_gap_ms.serve": None, "engine_steps.serve": None}
    assert phases.phase_seconds({"devices": {}, "scopes": SCOPES}, 0,
                                100) is None
    # scope paths without the phases, as the parent's program has them
    parent = dict(tr, scopes={n: "jit(step)/mul" for n in SCOPES})
    assert phases.phase_seconds(parent, 0, 100) is None
    # phases scoped, but none of their ops inside the window
    assert phases.phase_seconds(tr, 200, 300) is None


def test_slowest_step_and_what_it_held():
    tr = two_devices()
    tr["program_spans"] = PROGRAM + [("train.step", 100, 190, {"step": 2}),
                                     ("train.input", 100, 110, {}),
                                     ("train.fetch", 120, 190, {})]
    got = phases.slowest(tr, "train.step", 0, 200)
    assert got == {"ms": pytest.approx(90e-6), "args": {"step": 2},
                   "inside_ms": {"train.input": pytest.approx(10e-6),
                                 "train.fetch": pytest.approx(70e-6)}}
    assert phases.slowest(tr, "train.step", 0, 100)["args"] == {"step": 0}
    assert phases.slowest(tr, "serve.step", 0, 200) is None


def test_overlap_of_interval_lists():
    assert phases.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert phases.overlap([(0, 10)], [(10, 20)]) == 0
    assert phases.overlap([], [(0, 1)]) == 0


# -------------------------------------- the benchmark's readers unchanged
READERS = ["step_mfu.train", "device_idle.train", "device_idle.serve",
           "step_mfu.serve", "decode_step_ms.serve",
           "paged_attn_roofline.serve"]


@pytest.mark.parametrize("metric", READERS)
def test_program_spans_and_scopes_leave_the_readers_alone(metric):
    """The recorded v5e trace with the program's spans and every op's
    scope added reads exactly as without them."""
    base = json.loads((DATA / "trace_v5e_small.json").read_text())
    lo, hi = trace.window_of(base)
    program = [("serve.step", lo + 1000 * k, lo + 1000 * k + 900,
                {"active": 4, "queued": 0, "preempted": 0})
               for k in range(40)]
    extended = dict(base, spans=base["spans"] + [
        (n, s, e) for n, s, e, _ in program], program_spans=program,
        scopes={n: "jit(step)/jvp(forward)/x" for d in
                base["devices"].values() for n, _, _ in d["ops"]})
    cfg = common.config_file("deepseek-coder-33b-stage8")
    fam = common.family(cfg)
    r = {"lo": lo, "hi": hi, "steps": 3, "chips": 1, "family": fam,
         "dims": fam.Dims.from_config(cfg),
         "tokens_per_step": 8192, "seq_len": 4096,
         "device_kind": "TPU v5 lite", "kv_bytes": 2,
         "calls": {"bench.decode": [[100, 200, 300, 400]] * 3,
                   "bench.prefill": [[512]]}}
    read = common.load_reader(metric)
    want = read(dict(r, trace=base))
    assert want is not None
    assert read(dict(r, trace=extended)) == want


# ------------------------------------------------ a recorded v5e capture
def test_recorded_v5e_train_step():
    """One step of the program's own train step (the smoke-size
    minitron_4b RunSpec) on a TPU v5 lite, with its spans and scopes;
    trimmed to the planes, lines and events the readers use, the device
    ops named by their HLO names."""
    path = DATA / "trace_v5e_train_step.xplane.pb"
    tr = phases.load(str(path))
    old = trace.load_xplane(str(path))
    assert tr["devices"] == old["devices"]
    assert [s for s in tr["spans"] if s[0].startswith("bench.")] == \
        old["spans"]
    names = [(n, a) for n, _, _, a in tr["program_spans"]]
    assert names == [("train.step", {"step": 4}), ("train.input", {}),
                     ("train.dispatch", {}), ("train.fetch", {})]
    assert {phases.phase_of(p) for p in tr["scopes"].values()} == \
        set(phases.PHASES) | {None}
    (_, lo, hi, _), = tr["program_spans"][:1]
    got = phases.readings({"trace": tr, "lo": lo, "hi": hi, "steps": 1})
    assert got["forward_ms.train"] == pytest.approx(2.047227)
    assert got["backward_ms.train"] == pytest.approx(8.105701)
    assert got["optimizer_ms.train"] == pytest.approx(0.006465)
    assert got["grad_sync_ms.train"] == pytest.approx(0.010905)
    assert got["input_idle_ms.train"] == pytest.approx(3.332209)
    total = sum(got[f"{p}_ms.train"] for p in phases.PHASES)
    assert total == pytest.approx(got["busy_ms.train"], rel=0.005)


# -------------------------------------- the program's spans, captured here
def capture(tmp_path, fn):
    import glob

    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return phases.load(path)


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def tiny_spec(**kw):
    from repro.api import RunSpec
    from repro.data.pipeline import DataConfig
    return RunSpec(arch="minitron_4b", smoke=True, steps=3,
                   data=DataConfig(vocab=0, seq_len=32, global_batch=2,
                                   seed=0), **kw)


def test_train_step_spans(tmp_path):
    import jax

    from repro.api import TrainSession
    session = TrainSession(tiny_spec(), callbacks=[])
    with jax.set_mesh(session.mesh):
        session.run_step(0)
        tr = capture(tmp_path, lambda: session.run_step(1))
    spans = tr["program_spans"]
    assert [s[0] for s in spans] == ["train.step", "train.input",
                                     "train.dispatch", "train.fetch"]
    step = spans[0]
    assert step[3] == {"step": 1}
    assert all(inside(s, step) for s in spans[1:])
    assert spans[1][2] <= spans[2][1] and spans[2][2] <= spans[3][1]
    assert all(s[3] == {} for s in spans[1:])


def test_serve_step_spans(tmp_path):
    import numpy as np

    from repro.api import ServeConfig
    from repro.serving.engine import ServeEngine
    engine = ServeEngine(tiny_spec(serve=ServeConfig(
        page_size=4, max_active=4, max_seq=32, max_queue=8)))
    rng = np.random.default_rng(0)
    for n in (5, 9, 3):
        engine.submit(rng.integers(0, 64, n).tolist(), 4)
    engine.step()                       # compiles prefill and decode
    engine.submit(rng.integers(0, 64, 6).tolist(), 4)
    tr = capture(tmp_path, engine.step)
    spans = tr["program_spans"]
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    step, = by["serve.step"]
    assert step[3] == {"active": 3, "queued": 1, "preempted": 0}
    assert by["serve.admit"][0][3] == {"admitted": 1}
    prefill, = by["serve.prefill"]
    assert prefill[3] == {"rows": 1, "tokens": 6, "padded": 1 * 8}
    for name in ("serve.admit", "serve.prefill", "serve.grow", "serve.pack",
                 "serve.decode", "serve.sample", "serve.emit"):
        assert by[name] and all(inside(s, step) for s in by[name]), name
    # the prefill samples its row's first token; the decode step its own
    assert len(by["serve.sample"]) == 2
    assert sum(inside(s, prefill) for s in by["serve.sample"]) == 1
    assert not tr["devices"]            # a CPU capture: no TPU plane
