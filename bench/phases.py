"""The program's own spans and scopes in a profiler capture, and what they
read: a training step's device time by phase (forward, backward,
optimizer, gradient sync), and the device's idle time inside a host
phase (a training step's host input, a serving engine step's host work).

The program writes host spans named ``train.*`` and ``serve.*``
(``jax.profiler.TraceAnnotation``; counters ride on them as arguments)
and scopes its train step's ops with ``jax.named_scope``.  A device op's
scope path is its HLO ``op_name``, which the capture keeps in the op's
``SCOPE_STAT`` stat.  ``load`` reads a capture into
``bench.trace.load_xplane``'s neutral form, extended:

* ``spans`` also holds the program's spans as (name, start, end), so that
  ``bench.trace.idle_by_span`` names the program's phases;
* ``program_spans``: (name, start, end, args) of every program span;
* ``scopes``: each distinct device op name -> its scope path, read once.

The benchmark's ``--trace 1`` run keeps none of these.  This runs one cell
traced with them kept, and prints the benchmark's traced result line
with the program's readings beside it (``program``):

    python3 -m bench.phases --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import time

from . import common, trace

PROGRAM = ("train.", "serve.")
SCOPE_STAT = "tf_op"
PHASES = ("forward", "backward", "optimizer", "grad_sync")
_BACKWARD = re.compile(r"transpose\([^/]*\bforward\b")
_FORWARD = re.compile(r"\bforward\b")


def phase_of(path: str):
    """The train-step phase of an op from its scope path, or None:
    'grad_sync' if a component is grad_sync, else 'optimizer' if one is
    optimizer, else 'backward' if forward sits inside a transpose(, else
    'forward' if forward is there at all."""
    parts = path.split("/")
    if "grad_sync" in parts:
        return "grad_sync"
    if "optimizer" in parts:
        return "optimizer"
    if _BACKWARD.search(path):
        return "backward"
    if _FORWARD.search(path):
        return "forward"
    return None


# ------------------------------------------------------------------ load
def load(path: str) -> dict:
    """``bench.trace.load_xplane``'s neutral form of the capture at
    ``path``, with the program's spans and the device ops' scope paths."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    tr = trace.load_xplane(path)
    program = program_spans(ProfileData.from_serialized_xspace(data))
    tr["spans"] += [(n, s, e) for n, s, e, _ in program]
    tr["program_spans"] = program
    tr["scopes"] = op_scopes(data)
    return tr


def program_spans(pd) -> list:
    """(name, start, end, args) of each program span of a read capture."""
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM)]


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized protobuf
    message: an int for a varint or fixed-width field, a memoryview for a
    length-delimited one (string, bytes, message)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            v, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def op_scopes(data: bytes) -> dict:
    """Device op name -> its scope path, read once per op from the event
    metadata of the TPU planes of a serialized capture (an ``XSpace``):
    the ``SCOPE_STAT`` stat, less its ':<type>' ending.

    ``jax.profiler.ProfileData`` gives an event's own stats, not its
    metadata's, so this walks the protobuf: XSpace.planes (1); XPlane name
    (2), event_metadata (4) and stat_metadata (5), maps whose entries hold
    the key (1) and the value (2); XEventMetadata name (2) and stats (5);
    XStatMetadata id (1) and name (2); XStat metadata_id (1) and
    str_value (5).  Fields it does not name, the events among them, are
    skipped whole."""
    out: dict = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_ids = "", [], set()
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(v)
            elif g == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                if bytes(meta.get(2, b"")) == SCOPE_STAT.encode():
                    stat_ids.add(meta.get(1, 0))
        if not name.startswith("/device:TPU:") or not stat_ids:
            continue
        for entry in events:
            op = path = ""
            for h, w in _fields(dict(_fields(entry)).get(2, b"")):
                if h == 2:
                    op = bytes(w).decode()
                elif h == 5:
                    stat = dict(_fields(w))
                    if stat.get(1) in stat_ids and 5 in stat:
                        path = bytes(stat[5]).decode().rsplit(":", 1)[0]
            if path and not out.get(op):
                out[op] = path
    return out


# ------------------------------------------------------------- reductions
def overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def phase_seconds(tr: dict, lo, hi):
    """Per phase, and 'unscoped' (busy, but no phase's op running), the
    seconds of the union of the phase's op intervals inside [lo, hi],
    averaged over the devices; None where no op has a phase (a program
    without the scopes, or an executable that a compile cache keyed
    without metadata served from such a program)."""
    phase = {n: phase_of(p) for n, p in (tr.get("scopes") or {}).items()}
    if not any(phase.values()) or not tr["devices"]:
        return None
    out = dict.fromkeys(PHASES + ("unscoped",), 0.0)
    nd = len(tr["devices"])
    for d in tr["devices"].values():
        by: dict = {}
        for n, s, e in d["ops"]:
            by.setdefault(phase.get(n), []).append((s, e))
        for p in PHASES:
            out[p] += trace.covered(by.get(p, []), lo, hi) * 1e-9 / nd
        scoped = [x for p in PHASES for x in by.get(p, [])]
        busy = trace.covered([(s, e) for _, s, e in d["ops"]], lo, hi)
        out["unscoped"] += (busy - trace.covered(scoped, lo, hi)) * 1e-9 / nd
    return out if any(out[p] for p in PHASES) else None


def idle_in(tr: dict, name: str, lo, hi):
    """(seconds of device idle inside the program spans ``name`` that
    start in [lo, hi), averaged over the devices; how many such spans),
    or None where there are none, or no device op."""
    spans = trace.merge(trace.clip([(s, e) for n, s, e, _ in
                                    tr.get("program_spans", ())
                                    if n == name and lo <= s < hi], lo, hi))
    if not spans or not tr["devices"]:
        return None
    count = sum(1 for n, s, _, _ in tr["program_spans"]
                if n == name and lo <= s < hi)
    idle = sum(overlap(trace.gaps([(s, e) for _, s, e in d["ops"]], lo, hi),
                       spans) for d in tr["devices"].values())
    return idle * 1e-9 / len(tr["devices"]), count


def readings(r: dict) -> dict:
    """The program's readings of a traced window (``r`` as a per-layer
    reader gets it), each None where the capture lacks what it reads.

    Training (``r['steps']`` whole steps): each phase's device ms a step,
    'unscoped' and the busy ms a step, and the device's idle ms inside
    ``train.input`` a step.  Serving: the device's idle ms inside
    ``serve.step`` per engine step in the window."""
    tr, lo, hi = r["trace"], r["lo"], r["hi"]
    if r.get("steps"):
        n = r["steps"]
        per = phase_seconds(tr, lo, hi)
        out = {f"{p}_ms.train": 1e3 * per[p] / n if per else None
               for p in PHASES + ("unscoped",)}
        busy = trace.device_busy(tr, lo, hi)
        out["busy_ms.train"] = 1e3 * sum(busy) / max(1, len(busy)) / n
        idle = idle_in(tr, "train.input", lo, hi)
        out["input_idle_ms.train"] = 1e3 * idle[0] / n if idle else None
        return out
    idle = idle_in(tr, "serve.step", lo, hi)
    return {"host_gap_ms.serve": 1e3 * idle[0] / idle[1] if idle else None,
            "engine_steps.serve": idle[1] if idle else None}


def slowest(tr: dict, name: str, lo, hi):
    """The longest program span ``name`` that starts in [lo, hi), in ms,
    with the ms of each program span inside it, by name; None without
    one.  Says whether a stalled step's host was busy or waiting."""
    spans = [x for x in tr.get("program_spans", ())
             if x[0] == name and lo <= x[1] < hi]
    if not spans:
        return None
    _, s, e, args = max(spans, key=lambda x: x[2] - x[1])
    out = {"ms": (e - s) * 1e-6, "args": args, "inside_ms": {}}
    for n, a, b, _ in tr["program_spans"]:
        if s <= a and b <= e and (a, b) != (s, e):
            out["inside_ms"][n] = out["inside_ms"].get(n, 0.0) + (b - a) * 1e-6
    return out


# ------------------------------------------------------------------- run
class Capture(trace.Tracer):
    """The benchmark's tracer, reducing its capture with ``load``."""

    def reduce(self) -> dict:
        t = time.perf_counter()
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        try:
            self.tr = load(files[0]) if files else {"devices": {},
                                                    "spans": []}
            return self.tr
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.reduce_s = time.perf_counter() - t


def main(argv=None) -> int:
    from . import run
    args = run.parse(argv)
    cell = common.workload(args.workload)
    model_cfg = common.config_file(cell["config"])
    mix = common.traffic_file(cell["traffic"])
    sys.path.insert(0, str(common.SRC))
    try:
        devs = common.require_chips(cell["chips"])
    except common.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    common.enable_compile_cache()
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from . import serve_cell, train_cell
    kind = {"train": train_cell, "serve": serve_cell}[mix["kind"]]
    tracer = Capture()
    res, checks = kind.run(cell, model_cfg, mix, args.seed, args.seconds,
                           devs, common.CompileCounter(), tracer)
    metrics, extra, breakdown = run.per_layer(cell, res["readings"], tracer)
    lo, hi = trace.window_of(tracer.tr) or (0, 0)
    r = dict(res["readings"], trace=tracer.tr, calls=tracer.calls, lo=lo,
             hi=hi)
    print(json.dumps({"info": res.get("info")}), flush=True)
    print(json.dumps({
        "correct": common.all_within(checks), "metrics": metrics,
        "program": readings(r) if hi > lo else {},
        "slowest": {n: slowest(tracer.tr, n, lo, hi)
                    for n in ("train.step", "serve.step")},
        "reduce_s": tracer.reduce_s,
        "e2e": {k: v[0] for k, v in res["e2e"].items()},
        "device": dict(res["device"], **extra), "breakdown": breakdown,
        "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
