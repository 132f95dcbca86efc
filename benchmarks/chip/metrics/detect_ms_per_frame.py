"""Operators and batching: self time of ``detect`` spans per frame
consumed (padding rows not counted as frames)."""

from benchmarks.chip import tracereduce as tr


def read(run):
    return tr.per_unit_ms(run.spans, "detect", "frames")
