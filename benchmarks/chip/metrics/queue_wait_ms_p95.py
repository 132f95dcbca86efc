"""Server admission: 95th percentile of the wait from a request's due
time to the start of its ``query`` span (requests collapsed onto another's
execution have no span of their own and are not counted)."""

from benchmarks.chip import tracereduce as tr


def read(run):
    start = {sp["t"]: sp["t0"] for sp in tr.named(run.spans, "query")}
    waits = [start[r["trace_id"]] - r["due"] for r in run.records
             if r.get("trace_id") in start]
    if not waits:
        return None
    return 1e3 * tr.percentile(waits, 95)
