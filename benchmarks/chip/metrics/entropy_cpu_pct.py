"""Host entropy decode: the on-CPU share of the ``codec.entropy`` spans
(their threads' CPU time over their wall time)."""

from benchmarks.chip import hostlayers as hl


def read(run):
    return hl.cpu_pct(run.spans, "codec.entropy")
