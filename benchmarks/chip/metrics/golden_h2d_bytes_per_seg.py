"""Bytes the golden encode hands to the device: ``h2d_bytes`` of the
``ingest.golden`` spans (their children's included), per span."""

from benchmarks.chip import hostlayers as hl


def read(run):
    return hl.attr_per_unit(run.spans, "ingest.golden", "h2d_bytes")
