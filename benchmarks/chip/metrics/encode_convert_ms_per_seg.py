"""Golden encode, fidelity convert: self time of the ``encode.convert``
spans under ``ingest.golden``, per such span (one a segment)."""

from benchmarks.chip import hostlayers as hl


def read(run):
    return hl.self_ms_under(run.spans, "encode.convert", "ingest.golden")
