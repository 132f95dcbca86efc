"""Planner and cache: the share of ``retrieve`` spans served from the
decoded-segment cache (an exact or a richer entry)."""

from benchmarks.chip import tracereduce as tr


def read(run):
    sel = tr.named(run.spans, "retrieve")
    if not sel:
        return None
    hits = sum(1 for sp in sel if sp["a"].get("cache") in ("hit", "richer"))
    return 100.0 * hits / len(sel)
