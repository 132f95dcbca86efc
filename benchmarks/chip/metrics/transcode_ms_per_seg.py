"""Ingest, transcode: self time of ``ingest.transcode`` spans per derived
segment written."""

from benchmarks.chip import tracereduce as tr


def read(run):
    return tr.per_unit_ms(run.spans, "ingest.transcode")
