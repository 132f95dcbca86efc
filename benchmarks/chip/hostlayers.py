"""Readings inside the host layers, from the tallies and compile spans the
program's tracer adds to its spans (``cpu``, ``h2d_bytes`` and
``d2h_bytes`` attributes; ``jax.compile`` spans; the process's compile
counter), built on ``tracereduce``'s helpers.

A program whose tracer records none of these gives None everywhere, so
the readers stay silent there instead of reading zero."""

from __future__ import annotations

import contextlib

from . import tracereduce as tr

COMPILE_SPAN = "jax.compile"


def self_ms_under(spans: list[dict], name: str, parent: str
                  ) -> float | None:
    """Self time in milliseconds per ``name`` span whose parent is a
    ``parent`` span; None where there is none."""
    pids = {sp["s"] for sp in tr.named(spans, parent)}
    # same-named spans under other parents leave the list; no span of
    # ours is their child, so our self times do not change
    kept = [sp for sp in spans if sp["n"] != name or sp["p"] in pids]
    times = tr.self_times(kept, name)
    if not times:
        return None
    return 1e3 * sum(times) / len(times)


def attr_per_unit(spans: list[dict], name: str, attr: str,
                  unit_attr: str | None = None) -> float | None:
    """Sum of attribute ``attr`` over ``name`` spans (0 where a span lacks
    it), per span or per the sum of ``unit_attr`` (1 where a span lacks
    it).  None where no such span carries ``attr``."""
    sel = tr.named(spans, name)
    if not any(attr in sp["a"] for sp in sel):
        return None
    units = (len(sel) if unit_attr is None
             else sum(float(sp["a"].get(unit_attr, 1)) for sp in sel))
    if units <= 0:
        return None
    return sum(float(sp["a"].get(attr, 0)) for sp in sel) / units


def cpu_pct(spans: list[dict], name: str) -> float | None:
    """On-CPU share of ``name`` spans: their threads' CPU seconds over
    their wall seconds, in percent."""
    sel = tr.named(spans, name)
    wall = sum(sp["d"] for sp in sel)
    if not any("cpu" in sp["a"] for sp in sel) or wall <= 0:
        return None
    return 100.0 * sum(float(sp["a"].get("cpu", 0.0)) for sp in sel) / wall


class ShardCompiles:
    """The compile counters of a sharded run's shard processes read as one:
    every shard's compiles and cache loads, each ``t1`` on the harness's
    clock."""

    def __init__(self, events: list[dict]):
        self._events = sorted(events, key=lambda e: e["t1"])

    def events(self) -> list[dict]:
        return list(self._events)


# the shards' counter while a sharded run's metrics are read (see
# ``shard_compiles``); the harness process compiles nothing then
_SHARDS: list[ShardCompiles] = []


@contextlib.contextmanager
def shard_compiles(counter: ShardCompiles):
    """Within the block, ``compile_counter()`` is the shards' counter."""
    _SHARDS.append(counter)
    try:
        yield counter
    finally:
        _SHARDS.remove(counter)


def compile_counter():
    """The program's compile counter, or None where it has none; while a
    sharded run's metrics are read, its shards' (``shard_compiles``)."""
    if _SHARDS:
        return _SHARDS[-1]
    from repro.obs import trace as obs
    return getattr(obs, "COMPILES", None)


def window_compiles(spans: list[dict]) -> int:
    """Compiles and cache loads the program recorded in the traced
    window."""
    return len(tr.named(spans, COMPILE_SPAN))


def compile_s_before(events: list[dict], t: float) -> float:
    """Seconds of compiles and cache loads that ended by ``t`` (host
    ``perf_counter``)."""
    return sum(e["s"] for e in events if e["t1"] <= t)
