"""Share of a serving window in which no op runs on the device, in %,
averaged over the chips (bench.trace: 1 - busy union / window)."""
from bench import trace


def read(r):
    return trace.idle_share(r)
