"""Fidelity convert: self time of ``convert`` spans per segment
converted."""

from benchmarks.chip import tracereduce as tr


def read(run):
    return tr.per_unit_ms(run.spans, "convert")
