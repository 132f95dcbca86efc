"""95th percentile of request latency, each timed from its due time; a
request that failed or never answered counts as the longest wait the
run could see (the window plus the minute allowed after it)."""

from benchmarks.chip import tracereduce as tr
from benchmarks.chip.harness import LATE_S


def read(run):
    if not run.records:
        return None
    worst = run.window_s + LATE_S
    lat = [(r["end"] - r["due"]) if r.get("ok") else worst
           for r in run.records]
    return tr.percentile(lat, 95)
