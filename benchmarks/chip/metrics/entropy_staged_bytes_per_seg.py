"""Host entropy decode: bytes zeroed or copied into the symbol stack
beyond what the decompressor wrote there, per segment decoded
(``staged_bytes`` of the ``codec.entropy`` spans).  Silent on a program
whose spans do not carry it."""

from benchmarks.chip import hostlayers as hl


def read(run):
    return hl.attr_per_unit(run.spans, "codec.entropy", "staged_bytes",
                            "segments")
