"""Bytes the fidelity convert hands to the device: ``h2d_bytes`` of the
``convert`` spans per frame converted."""

from benchmarks.chip import hostlayers as hl


def read(run):
    return hl.attr_per_unit(run.spans, "convert", "h2d_bytes", "frames")
