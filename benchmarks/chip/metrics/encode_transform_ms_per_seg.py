"""Golden encode, forward transform: self time of the ``codec.transform``
spans under ``ingest.golden`` (chunks up, ``_encode_chunk``, symbols
down), per such span."""

from benchmarks.chip import hostlayers as hl


def read(run):
    return hl.self_ms_under(run.spans, "codec.transform", "ingest.golden")
