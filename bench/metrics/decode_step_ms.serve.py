"""Device time of one compiled decode step, in ms: the mean duration of
the program executions in the window that run the paged-attention
kernel (the decode step is the only program that does)."""
from bench import trace


def read(r):
    runs = trace.module_runs_with(r["trace"], r["lo"], r["hi"],
                                  trace.PAGED_KERNEL)
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
