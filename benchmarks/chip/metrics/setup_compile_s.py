"""Seconds of set-up spent compiling or loading programs from the compile
cache: the program's compile counter, summed over the compiles that ended
before the window's first request or segment."""

from benchmarks.chip import hostlayers as hl


def read(run):
    counter = hl.compile_counter()
    if counter is None or not run.records:
        return None
    start = min(r["due"] for r in run.records)
    return hl.compile_s_before(counter.events(), start)
