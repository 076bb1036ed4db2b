"""Device trace: capture (JAX profiler) and the reduction from trace to
per-layer readings.

The capture writes an ``.xplane.pb`` under a temporary directory;
``load_xplane`` turns it into a neutral form -- per device, its op
intervals; and the benchmark's own host spans (``bench.*``), all on the
profiler's one clock, in nanoseconds.  Everything after that is plain
interval arithmetic on lists, kept here so that every change reduces a trace
the same way:

* busy time: the union of a device's op intervals inside the window;
* idle gaps: the window minus that union, each gap attributed to the
  innermost benchmark span that covers its midpoint;
* exposed collective time: the part of the collective ops' intervals
  that no compute op on the same device covers.  A loop or call op
  (``while``, ``conditional``, ``call``) spans the ops of its body, which
  the trace lists as well, so it is neither: a collective inside a loop
  is exposed unless a compute op of the body runs beside it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile

# the paged decode kernel in the trace: the serving cells' only Pallas
# kernel, a TPU custom call inside the decode program
PAGED_KERNEL = r'custom_call_target="tpu_custom_call"' 
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter", re.I)
CONTAINERS = ("while", "conditional", "call")
_KIND = re.compile(r"[ )]([a-z][a-z0-9-]*)\(")


def op_kind(op: str) -> str:
    """'%fusion.12 = bf16[2,4096]{...} fusion(...), ...' -> 'fusion'; an
    op given by its name alone is its own kind."""
    if " = " not in op:
        return op
    kind = _KIND.search(op.split(" = ", 1)[1])
    return kind.group(1) if kind else ""


def is_collective(op: str) -> bool:
    """By the op's name or kind, never by its operands' names."""
    return bool(COLLECTIVE.search(op.split(" = ", 1)[0])
                or COLLECTIVE.search(op_kind(op)))


# ----------------------------------------------------------- interval math
def merge(intervals) -> list:
    """Sorted, non-overlapping union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals, lo, hi) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def gaps(intervals, lo, hi) -> list:
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def uncovered(intervals, cover, lo, hi) -> float:
    """Length of the union of ``intervals`` that ``cover`` leaves open, in
    one pass over both unions (a trace holds millions of ops)."""
    cov = merge(clip(cover, lo, hi))
    total, j = 0.0, 0
    for s, e in merge(clip(intervals, lo, hi)):
        total += e - s
        while j < len(cov) and cov[j][1] <= s:
            j += 1
        k = j
        while k < len(cov) and cov[k][0] < e:
            total -= min(e, cov[k][1]) - max(s, cov[k][0])
            k += 1
    return total


# ------------------------------------------------------------- reductions
def window_of(tr: dict, name: str = "bench.window"):
    """(start, end) of the benchmark's window span in trace time."""
    spans = [(s, e) for n, s, e in tr["spans"] if n == name]
    if not spans:
        return None
    return spans[0]


def device_busy(tr: dict, lo, hi) -> list:
    """Per device, seconds in which an op ran inside [lo, hi]."""
    return [covered([(s, e) for _, s, e in d["ops"]], lo, hi) * 1e-9
            for d in tr["devices"].values()]


def collective_exposed(tr: dict, lo, hi) -> list:
    """Per device, seconds of collective ops not covered by compute
    (loop and call ops are neither)."""
    out, role = [], {}          # op name -> "coll", "comp" or "loop"
    for d in tr["devices"].values():
        ivs = {"coll": [], "comp": [], "loop": []}
        for n, s, e in d["ops"]:
            if n not in role:
                role[n] = ("loop" if op_kind(n) in CONTAINERS else
                           "coll" if is_collective(n) else "comp")
            ivs[role[n]].append((s, e))
        out.append(uncovered(ivs["coll"], ivs["comp"], lo, hi) * 1e-9)
    return out


def op_seconds(tr: dict, lo, hi, pattern=None) -> dict:
    """Seconds per op name inside [lo, hi], averaged over devices."""
    tot: dict = {}
    rx = re.compile(pattern) if pattern else None
    nd = max(1, len(tr["devices"]))
    for d in tr["devices"].values():
        for n, s, e in d["ops"]:
            if rx is not None and not rx.search(n):
                continue
            c = min(e, hi) - max(s, lo)
            if c > 0:
                tot[n] = tot.get(n, 0.0) + c * 1e-9 / nd
    return tot


def idle_by_span(tr: dict, lo, hi) -> dict:
    """Idle seconds of each device's gaps, averaged over devices, by the
    innermost benchmark span covering each gap's midpoint ('none' where
    no span covers it).  Spans are taken shortest first, each naming the
    gaps it covers that no shorter span has named."""
    spans = sorted((x for x in tr["spans"] if x[0] != "bench.window"),
                   key=lambda x: x[2] - x[1])
    out: dict = {}
    nd = max(1, len(tr["devices"]))
    for d in tr["devices"].values():
        idle = gaps([(a, b) for _, a, b in d["ops"]], lo, hi)
        mids = [(s + e) / 2 for s, e in idle]     # sorted, as the gaps are
        names = [None] * len(idle)
        for n, a, b in spans:
            for i in range(bisect.bisect_left(mids, a),
                           bisect.bisect_right(mids, b)):
                if names[i] is None:
                    names[i] = n
        for (s, e), n in zip(idle, names):
            n = n or "none"
            out[n] = out.get(n, 0.0) + (e - s) * 1e-9 / nd
    return out


def idle_share(r: dict):
    """100 * (1 - busy / window), busy averaged over the devices; None
    where the trace holds no device op."""
    tr = r["trace"]
    busy = device_busy(tr, r["lo"], r["hi"])
    if not busy or not any(busy):
        return None
    return 100.0 * (1.0 - (sum(busy) / len(busy)) / ((r["hi"] - r["lo"])
                                                      * 1e-9))


def module_runs_with(tr: dict, lo, hi, pattern: str) -> list:
    """Durations (s) of program executions inside [lo, hi] during which
    an op matching ``pattern`` ran on the same device."""
    rx = re.compile(pattern)
    out = []
    for d in tr["devices"].values():
        hits = sorted(s for n, s, _ in d["ops"] if rx.search(n))
        for _, s, e in d.get("modules", []):
            if s < lo or e > hi:
                continue
            i = bisect.bisect_left(hits, s)
            if i < len(hits) and hits[i] < e:
                out.append((e - s) * 1e-9)
    return out


def short_name(op: str) -> str:
    """'%fusion.12 = bf16[2,4096]{...} fusion(...), ...' -> 'fusion.12
    fusion bf16[2,4096]': the op, its kind and its first result array
    with dimensions."""
    if " = " not in op:
        return op[:100]
    lhs, rhs = op.split(" = ", 1)
    kind = _KIND.search(rhs)
    head = rhs[:kind.start()] if kind else rhs
    typ = re.search(r"[a-z0-9]+\[[0-9][0-9,]*\]", head)
    return " ".join(x for x in (lhs.lstrip("%"),
                                kind.group(1) if kind else "",
                                typ.group(0) if typ else "") if x)[:100]


def top(d: dict, k: int = 10) -> list:
    return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:k]]


# ---------------------------------------------------------------- capture
def load_xplane(path: str) -> dict:
    """Neutral form of one profiler capture: per TPU plane its op
    intervals ('XLA Ops' line) and program executions ('XLA Modules'),
    and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"XLA Ops": [], "XLA Modules": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] += [(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns)
                                         for ev in line.events]
            devices[plane.name] = {"ops": lines["XLA Ops"],
                                   "modules": lines["XLA Modules"]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns
                        spans.append((ev.name, s, s + ev.duration_ns))
    return {"devices": devices, "spans": spans}


class Tracer:
    """One profiler capture around the window, written to a temporary
    directory that ``reduce`` reads and removes."""

    def __init__(self):
        import jax
        self._jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.calls: dict = {}
        self.recording = False

    def span(self, name: str):
        return self._jax.profiler.TraceAnnotation(name)

    def start(self):
        self._jax.profiler.start_trace(self.dir)
        self.recording = True

    def stop(self):
        if self.recording:
            self.recording = False
            self._jax.profiler.stop_trace()

    def wrap(self, obj, attr: str, name: str, record=None):
        """Put a host span (and an optional record of the arguments)
        around ``obj.attr``; the wrapped callable is unchanged."""
        fn = getattr(obj, attr)
        calls = self.calls.setdefault(name, [])

        def wrapped(*a, **kw):
            if record is not None and self.recording:
                calls.append(record(*a, **kw))
            with self.span(name):
                return fn(*a, **kw)
        setattr(obj, attr, wrapped)

    def wrap_train(self, session):
        self.wrap(session.data, "batch", "bench.input")

    def wrap_serve(self, engine):
        """Spans around the engine's layers; each prefill call records the
        length of every row it admits, each decode call the cached length
        of every active sequence it advances."""
        self.wrap(engine.sched, "admit", "bench.admit")
        self.wrap(engine, "_prefill_batch", "bench.prefill",
                  record=lambda seqs: [len(q.req.prompt) + len(q.req.generated)
                                       for q in seqs])
        self.wrap(engine, "_decode", "bench.decode", record=lambda *a, **k: [
            s.length for s in engine.sched.active])
        self.wrap(engine, "_sample", "bench.sample")

    def reduce(self) -> dict:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        try:
            if not files:
                return {"devices": {}, "spans": []}
            return load_xplane(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

