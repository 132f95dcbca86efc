"""Host entropy decode: self time of ``codec.entropy`` spans per segment
decoded."""

from benchmarks.chip import tracereduce as tr


def read(run):
    return tr.per_unit_ms(run.spans, "codec.entropy", "segments")
