"""Exposed collective time of a training step, in ms: per chip, the part
of the collective ops' intervals in the traced window during which no
compute op runs on that chip (bench.trace.collective_exposed; a loop op
spans its body's collectives and covers none of them), averaged over the
chips, over the whole steps in the window."""
from bench import trace


def read(r):
    if not r.get("steps") or r["hi"] <= r["lo"]:
        return None
    tr = r["trace"]
    if not any(trace.is_collective(n) for d in tr["devices"].values()
               for n, _, _ in d["ops"]):
        return None
    exposed = trace.collective_exposed(tr, r["lo"], r["hi"])
    return 1e3 * sum(exposed) / len(exposed) / r["steps"]
