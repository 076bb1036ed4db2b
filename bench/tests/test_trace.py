"""The reduction from trace to per-layer readings."""
import pytest

from bench import trace

# one device, times in ns; the window span is [0, 40]
TR = {
    "devices": {"/device:TPU:0": {
        "ops": [("fusion.1", 0, 10), ("fusion.2", 5, 15),
                ("all-reduce.3", 12, 22), ("fusion.4", 20, 30)],
        "modules": [("jit_step", 0, 16), ("jit_step", 19, 31)],
    }},
    "spans": [("bench.window", 0, 40), ("bench.run_step", 0, 40),
              ("bench.input", 21.5, 23.5), ("bench.input", 32, 40)],
}


def test_interval_union_and_gaps():
    assert trace.merge([(5, 15), (0, 10), (20, 30), (30, 31)]) == \
        [(0, 15), (20, 31)]
    assert trace.covered([(0, 10), (5, 15), (20, 30)], 0, 40) == 25
    assert trace.covered([(0, 10)], 4, 6) == 2
    assert trace.gaps([(0, 10), (5, 15), (20, 30)], 0, 40) == \
        [(15, 20), (30, 40)]
    assert trace.uncovered([(10, 20)], [(0, 12), (18, 25)], 0, 40) == 6


def test_busy_idle_and_window():
    lo, hi = trace.window_of(TR)
    assert (lo, hi) == (0, 40)
    assert trace.device_busy(TR, lo, hi) == [pytest.approx(30e-9)]
    r = {"trace": TR, "lo": lo, "hi": hi}
    assert trace.idle_share(r) == pytest.approx(25.0)


def test_exposed_collective_time():
    # all-reduce [12, 22] is covered by fusion.2 up to 15, fusion.4 from 20
    assert trace.collective_exposed(TR, 0, 40) == [pytest.approx(5e-9)]


def test_idle_gaps_go_to_the_innermost_span():
    # gaps [30, 40] (mid 35, inside bench.input [32, 40])
    by = trace.idle_by_span(TR, 0, 40)
    assert by == {"bench.input": pytest.approx(10e-9)}
    tr = dict(TR, spans=[("bench.window", 0, 40), ("bench.run_step", 0, 40)])
    assert trace.idle_by_span(tr, 0, 40) == {
        "bench.run_step": pytest.approx(10e-9)}


def test_op_seconds_and_modules():
    per = trace.op_seconds(TR, 0, 40)
    assert per["all-reduce.3"] == pytest.approx(10e-9)
    assert trace.top(per, 2)[0][0] in ("fusion.1", "fusion.2", "fusion.4",
                                       "all-reduce.3")
    assert trace.op_seconds(TR, 0, 40, r"all-") == {
        "all-reduce.3": pytest.approx(10e-9)}
    runs = trace.module_runs_with(TR, 0, 40, r"all-reduce")
    assert runs == [pytest.approx(16e-9)]


def test_train_readers_on_the_small_trace():
    """step_mfu.train divides by the trace's busy time per step, and the
    exposed collective time is per step."""
    from bench import common
    cfg = common.config_file("minitron-4b-stage4")
    fam = common.family(cfg)
    dims = fam.Dims.from_config(cfg)
    r = {"trace": TR, "lo": 0, "hi": 40, "steps": 2, "chips": 1,
         "tokens_per_step": 8192, "seq_len": 4096, "family": fam,
         "dims": dims, "device_kind": "TPU v5 lite"}
    per_step = fam.train_flops_per_token(dims, 4096) * 8192
    mfu = common.load_reader("step_mfu.train")(r)
    assert mfu == pytest.approx(100 * per_step / (15e-9 * 197e12))
    exposed = common.load_reader("collective_exposed_ms.train")(r)
    assert exposed == pytest.approx(1e3 * 5e-9 / 2)
    tr = {"devices": {"/device:TPU:0": {"ops": [("fusion.1", 0, 10)]}},
          "spans": []}
    assert common.load_reader("collective_exposed_ms.train")(
        dict(r, trace=tr)) is None
    assert common.load_reader("step_mfu.train")(dict(r, steps=0)) is None


def test_no_device_ops_reads_nothing():
    tr = {"devices": {}, "spans": [("bench.window", 0, 10)]}
    assert trace.idle_share({"trace": tr, "lo": 0, "hi": 10}) is None


def test_recorded_v5e_trace():
    """A capture from a TPU v5 lite (three rounds of a jitted bf16 matmul
    and of the paged-attention kernel, each fetched before the next),
    reduced as a run reduces it."""
    import json
    import pathlib
    path = pathlib.Path(__file__).parent / "data" / "trace_v5e_small.json"
    tr = json.loads(path.read_text())
    lo, hi = trace.window_of(tr)
    assert (hi - lo) * 1e-9 == pytest.approx(5.26062e-3)
    assert trace.device_busy(tr, lo, hi) == [pytest.approx(7.93521e-4)]
    assert trace.idle_share({"trace": tr, "lo": lo, "hi": hi}) == \
        pytest.approx(84.9158, abs=1e-3)
    kernel = trace.op_seconds(tr, lo, hi, trace.PAGED_KERNEL)
    assert list(kernel) and all("custom-call(" in n for n in kernel)
    assert sum(kernel.values()) == pytest.approx(5.86339e-4)
    runs = trace.module_runs_with(tr, lo, hi, trace.PAGED_KERNEL)
    assert runs == [pytest.approx(1.96911e-4), pytest.approx(1.96925e-4),
                    pytest.approx(1.96632e-4)]
    assert trace.collective_exposed(tr, lo, hi) == [0.0]
    assert set(trace.idle_by_span(tr, lo, hi)) == {"none"}


def uncovered_by_definition(intervals, cover, lo, hi):
    return sum((e - s) - trace.covered(cover, s, e)
               for s, e in trace.merge(trace.clip(intervals, lo, hi)))


def idle_by_span_by_definition(tr, lo, hi):
    spans = sorted(tr["spans"], key=lambda x: x[2] - x[1])
    out = {}
    nd = max(1, len(tr["devices"]))
    for d in tr["devices"].values():
        for s, e in trace.gaps([(a, b) for _, a, b in d["ops"]], lo, hi):
            mid = (s + e) / 2
            name = next((n for n, a, b in spans
                         if a <= mid <= b and n != "bench.window"), "none")
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9 / nd
    return out


def random_trace(rng):
    devices = {}
    for d in range(rng.randint(1, 4)):
        ops = []
        for _ in range(rng.randint(0, 60)):
            s = rng.randint(0, 1000)
            name = rng.choice(["fusion.1", "all-gather.2", "all-reduce-start",
                               "convolution.3"])
            ops.append((name, s, s + rng.randint(0, 80)))
        devices[f"/device:TPU:{d}"] = {"ops": ops}
    spans = [("bench.window", 100, 900)]
    for k in range(rng.randint(0, 12)):
        s = rng.randint(0, 1000)
        spans.append((rng.choice(["bench.run_step", "bench.input", "x"]), s,
                      s + rng.randint(0, 300)))
    return {"devices": devices, "spans": spans}


@pytest.mark.parametrize("seed", range(20))
def test_sweeps_match_their_definitions(seed):
    """``uncovered`` and ``idle_by_span`` (one pass over a trace's
    millions of ops) read exactly what their definitions read."""
    import random
    tr = random_trace(random.Random(seed))
    for d in tr["devices"].values():
        coll = [(s, e) for n, s, e in d["ops"] if trace.COLLECTIVE.search(n)]
        comp = [(s, e) for n, s, e in d["ops"]
                if not trace.COLLECTIVE.search(n)]
        assert trace.uncovered(coll, comp, 100, 900) == \
            uncovered_by_definition(coll, comp, 100, 900)
    assert trace.idle_by_span(tr, 100, 900) == \
        idle_by_span_by_definition(tr, 100, 900)


def test_loop_ops_cover_no_collective():
    """A trace lists a loop op and the ops of its body: the loop covers
    none of the body's collectives, and an op that reads a collective's
    result is compute."""
    ops = [("%while.3 = (f32[8]) while(%tuple.1), body=%b", 0, 100),
           ("%fusion.1 = f32[8] fusion(%p)", 0, 30),
           ("%all-reduce.4 = u32[8] all-reduce(%fusion.1)", 30, 60),
           ("%fusion.2 = f32[8] fusion(%all-reduce.4)", 50, 70),
           ("%all-gather-start.5 = f32[8] all-gather-start(%x)", 80, 90)]
    tr = {"devices": {"/device:TPU:0": {"ops": ops}}, "spans": []}
    assert [trace.op_kind(n) for n, _, _ in ops] == [
        "while", "fusion", "all-reduce", "fusion", "all-gather-start"]
    assert [trace.is_collective(n) for n, _, _ in ops] == [
        False, False, True, False, True]
    assert trace.collective_exposed(tr, 0, 100) == [pytest.approx(30e-9)]
