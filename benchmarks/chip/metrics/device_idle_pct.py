"""Device: the share of the traced slice in which no program ran on the
device (1 - busy / window, from the profiler's trace).  Over the chips of
a sharded cell: 1 - the busy time of all chips / the sum of their
slices."""

from benchmarks.chip import tracereduce as tr


def read(run):
    traces = run.traces or run.trace
    if not traces:
        return None
    return tr.idle_pct(traces)
