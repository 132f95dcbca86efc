"""Segment store: self time of ``store.get`` spans per segment read."""

from benchmarks.chip import tracereduce as tr


def read(run):
    return tr.per_unit_ms(run.spans, "store.get")
