"""From spans and a device trace to numbers.

Two inputs, kept as plain data so that a small recorded sample can test
every function here:

* spans: the program's obs spans in their wire form, dicts with ``n``
  (name), ``s`` (span id), ``p`` (parent id), ``t0`` and ``d`` (seconds
  on the host's ``perf_counter``) and ``a`` (attributes);
* a device trace: ``{"chips": [{"modules": [[name, start_ns, dur_ns],
  ...]}], "anchor_ns": ..., "anchor_t": ..., "t0_ns": ..., "t1_ns":
  ...}``, read from the profiler's ``.xplane.pb`` by ``load_xplane``.
  ``modules`` are the device's "XLA Modules" events: one per program
  run, named ``jit_<function>(<fingerprint>)``.  ``anchor_ns`` is the
  trace time of an annotation the benchmark wrote at host time
  ``anchor_t``, which puts spans and device events on one clock.

A sharded cell has one device trace per chip, each from the process that
holds that chip and on that process's trace clock, with ``pid`` naming
the spans of that process (shard *i*'s are ``i + 1``).  The functions that
take a trace also take such a list: they sum over it, and on a single
trace (or a list of one) give exactly what they give on that trace.
"""

from __future__ import annotations

import re

MODULE_LINE = "XLA Modules"
ANCHOR = "benchmark.anchor"


# -- intervals ------------------------------------------------------------------

def merge(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for a, b in merge(intervals))


# -- spans ----------------------------------------------------------------------

def self_times(spans: list[dict], name: str) -> list[float]:
    """Each ``name`` span's duration less the part of it that its child
    spans (any thread) cover."""
    kids: dict[int, list] = {}
    for sp in spans:
        kids.setdefault(sp["p"], []).append((sp["t0"], sp["t0"] + sp["d"]))
    out = []
    for sp in spans:
        if sp["n"] != name:
            continue
        lo, hi = sp["t0"], sp["t0"] + sp["d"]
        out.append(sp["d"] - covered(kids.get(sp["s"], ()), lo, hi))
    return out


def named(spans: list[dict], name: str) -> list[dict]:
    return [sp for sp in spans if sp["n"] == name]


def per_unit_ms(spans: list[dict], name: str, unit_attr: str | None = None
                ) -> float | None:
    """Self time of ``name`` spans in milliseconds per unit: per span, or
    per the sum of attribute ``unit_attr`` (1 where a span lacks it).
    None where no such span ran."""
    sel = named(spans, name)
    if not sel:
        return None
    units = (len(sel) if unit_attr is None
             else sum(float(sp["a"].get(unit_attr, 1)) for sp in sel))
    if units <= 0:
        return None
    return 1e3 * sum(self_times(spans, name)) / units


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in [0, 100])."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    k = max(0, min(len(xs) - 1, -(-len(xs) * q // 100) - 1))
    return xs[int(k)]


# -- device trace ----------------------------------------------------------------

def load_xplane(path: str, t0_ns: float | None = None,
                t1_ns: float | None = None) -> dict:
    """Read a profiler ``.xplane.pb``: every TPU device plane's "XLA
    Modules" events, and the host annotation ``ANCHOR``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, anchor = [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods = []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    mods = [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events]
            chips.append({"plane": plane.name, "modules": mods})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor = float(e.start_ns)
    return {"chips": chips, "anchor_ns": anchor}


def window_ns(trace: dict) -> tuple[float, float]:
    """The traced window on the trace's clock: ``t0_ns``/``t1_ns`` as the
    benchmark set them."""
    return trace["t0_ns"], trace["t1_ns"]


def as_list(traces) -> list[dict]:
    """One trace, or a list of them (one per chip), as a list."""
    return [traces] if isinstance(traces, dict) else list(traces)


def _busy_ns(trace: dict) -> float:
    """Nanoseconds of the window in which a program ran, summed over the
    trace's chips."""
    lo, hi = window_ns(trace)
    return sum(covered([(s, s + d) for _n, s, d in chip["modules"]], lo, hi)
               for chip in trace["chips"])


def busy_s(traces) -> float:
    """Seconds of the window in which a program ran on the device,
    averaged over the chips."""
    traces = as_list(traces)
    chips = sum(len(t["chips"]) for t in traces)
    if not chips:
        return 0.0
    return sum(_busy_ns(t) for t in traces) / chips / 1e9


def slice_s(traces) -> float:
    """The traced window's length in seconds, averaged over the chips."""
    traces = as_list(traces)
    chips = sum(len(t["chips"]) for t in traces)
    if not chips:
        return 0.0
    return sum(len(t["chips"]) * (t["t1_ns"] - t["t0_ns"])
               for t in traces) / chips / 1e9


def idle_pct(traces) -> float | None:
    """1 - busy / window in percent: over several chips, the busy time of
    all of them over the sum of their windows."""
    traces = as_list(traces)
    if any(hi <= lo for lo, hi in map(window_ns, traces)) or not any(
            t["chips"] for t in traces):
        return None
    busy = sum(len(t["chips"]) * busy_s(t) for t in traces)
    window = sum(len(t["chips"]) * (t["t1_ns"] - t["t0_ns"]) for t in traces)
    return 100.0 * (1.0 - busy * 1e9 / window)


def module_name(name: str) -> str:
    """``jit__decode(123)`` -> ``jit__decode``."""
    return re.sub(r"\(\d+\)$", "", name)


def module_runs(traces, patterns: tuple[str, ...]) -> list[tuple]:
    """``(name, start_ns, dur_ns)`` of the program runs, on every chip,
    that start inside the window and whose name is ``jit_<p>`` for a
    pattern ``p``."""
    want = {f"jit_{p}" for p in patterns}
    out = []
    for trace in as_list(traces):
        lo, hi = window_ns(trace)
        out += [(n, s, d) for chip in trace["chips"]
                for n, s, d in chip["modules"]
                if lo <= s < hi and module_name(n) in want]
    return out


def top_modules(traces, k: int = 10) -> list[list]:
    """The ``k`` programs that took the most device time in the window,
    ``[[name, seconds], ...]``, summed over chips."""
    agg: dict[str, float] = {}
    for trace in as_list(traces):
        lo, hi = window_ns(trace)
        for chip in trace["chips"]:
            for n, s, d in chip["modules"]:
                part = max(0.0, min(s + d, hi) - max(s, lo))
                if part > 0:
                    agg[module_name(n)] = agg.get(module_name(n), 0.0) + part
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in top]


def idle_gaps(traces, spans: list[dict], k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps in the window of the first chip of each
    trace, each named by what the host was doing at its middle: the
    innermost span open then (the one that started last), else ``"no
    span"``.  A trace with a ``pid`` is named from that process's spans
    only.  ``[[name, seconds], ...]``, longest first."""
    gaps = []
    for trace in as_list(traces):
        own = (spans if "pid" not in trace
               else [sp for sp in spans if sp.get("pid") == trace["pid"]])
        gaps += [(b - a, trace, own, a, b) for a, b in _gaps(trace)]
    gaps = sorted(gaps, key=lambda g: -g[0])[:k]
    out = []
    for _len, trace, own, a, b in gaps:
        mid_t = host_time(trace, (a + b) / 2)
        best = None
        for sp in own:
            if sp["t0"] <= mid_t <= sp["t0"] + sp["d"]:
                if best is None or sp["t0"] > best["t0"]:
                    best = sp
        out.append([best["n"] if best else "no span", (b - a) / 1e9])
    return out


def _gaps(trace: dict) -> list[tuple[float, float]]:
    """The idle intervals of a trace's first chip inside its window."""
    lo, hi = window_ns(trace)
    if not trace["chips"]:
        return []
    busy = merge([(s, s + d) for _n, s, d in trace["chips"][0]["modules"]])
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    return [g for g in gaps if g[1] > g[0]]


def host_time(trace: dict, t_ns: float) -> float:
    """A trace time as host ``perf_counter`` seconds, by the anchor."""
    return trace["anchor_t"] + (t_ns - trace["anchor_ns"]) / 1e9
