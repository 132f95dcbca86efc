"""Bytes a golden decode hands to the device: ``h2d_bytes`` of the
``codec.decode`` spans per segment decoded."""

from benchmarks.chip import hostlayers as hl


def read(run):
    return hl.attr_per_unit(run.spans, "codec.decode", "h2d_bytes",
                            "segments")
