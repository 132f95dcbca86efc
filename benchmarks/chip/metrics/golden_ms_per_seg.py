"""Ingest, golden: self time of ``ingest.golden`` spans per segment."""

from benchmarks.chip import tracereduce as tr


def read(run):
    return tr.per_unit_ms(run.spans, "ingest.golden")
