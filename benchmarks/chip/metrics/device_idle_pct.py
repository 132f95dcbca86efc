"""Device: the share of the traced slice in which no program ran on the
device (1 - busy / window, from the profiler's trace)."""

from benchmarks.chip import tracereduce as tr


def read(run):
    if run.trace is None:
        return None
    return tr.idle_pct(run.trace)
