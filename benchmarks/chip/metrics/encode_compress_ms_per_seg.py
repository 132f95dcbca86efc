"""Golden encode, host entropy coding: self time of the ``codec.compress``
spans (zstd of the chunks) under ``ingest.golden``, per such span."""

from benchmarks.chip import hostlayers as hl


def read(run):
    return hl.self_ms_under(run.spans, "codec.compress", "ingest.golden")
