"""Structured trace spans over a bounded per-process ring buffer.

The data path (store read -> blob parse -> entropy/residual decode ->
spatial convert -> batched detect), the serving layers above it, and the
ingest machinery all emit *spans*: named intervals with a parent link and
a small dict of scalar attributes (bytes, chunks, cf name, hit kind ...).
Spans form per-thread stacks (``threading.local``) so nesting needs no
plumbing, and finished spans land in a fixed-capacity ring — tracing a
long-running server bounds memory by construction, at the cost of losing
the oldest spans.

Disabled cost is one attribute read plus a shared no-op context manager:
``span()`` returns the ``_NOOP`` singleton without allocating, so leaving
instrumentation in hot paths is free enough to keep everywhere (the
``obs_overhead`` bench gates this, < 3% on the full query path).

Spans also see inside a layer.  Each thread keeps running tallies:
``count("h2d_bytes", n)`` / ``count("d2h_bytes", n)`` at every host<->device
crossing add the bytes that crossed, and the thread's CPU clock
(``time.thread_time``) is read at span enter and exit.  A span records, as
attributes, how much of each moved while it was open (``cpu`` seconds,
``h2d_bytes``, ``d2h_bytes``), so a parent's figures include its children's
on the same thread.  ``COMPILES`` counts the XLA programs the process
compiles or loads from JAX's persistent cache and, while tracing is on,
records each as a ``jax.compile`` span.

Cross-process timelines: span/trace ids embed a per-process random salt,
so ids minted on different shard workers never collide; workers ship
finished spans as plain dicts next to their ``QueryResult`` wire forms and
the router ``absorb``s them — re-based onto the router's clock via the
per-host offset measured at ``hello`` — into its own ring.  One
``export_trace`` then writes a single Chrome trace-event JSON
(Perfetto-loadable) covering the whole cluster.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import threading
import time
from collections import deque

_log = logging.getLogger(__name__)


class Span:
    """One finished interval.  ``t0`` is ``time.perf_counter()`` seconds
    (re-based by ``Tracer.absorb`` when crossing processes); ids are
    64-bit ints (32-bit per-process salt << 32 | counter)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0", "dur",
                 "pid", "tid", "attrs")

    def __init__(self, name, trace_id, span_id, parent_id, t0, dur,
                 pid, tid, attrs):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.dur = dur
        self.pid = pid
        self.tid = tid
        self.attrs = attrs

    def to_wire(self) -> dict:
        """Msgpack-safe dict (short keys; attrs coerced to scalars)."""
        return {"n": self.name, "t": self.trace_id, "s": self.span_id,
                "p": self.parent_id, "t0": self.t0, "d": self.dur,
                "pid": self.pid, "tid": self.tid,
                "a": {k: (v if isinstance(v, (str, int, float, bool))
                          else str(v))
                      for k, v in self.attrs.items()}}

    @staticmethod
    def from_wire(d: dict) -> "Span":
        return Span(d["n"], int(d["t"]), int(d["s"]), int(d["p"]),
                    float(d["t0"]), float(d["d"]), int(d["pid"]),
                    int(d["tid"]), dict(d.get("a") or {}))


class _Noop:
    """Shared do-nothing span handle (the disabled-path return value)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _Noop()


class _SpanCM:
    """Live span handle: context manager that resolves its parent from the
    thread's span stack (falling back to an ``activate``d remote context)
    on enter and records into the tracer's ring on exit.  ``set`` adds
    attributes discovered mid-span (hit kind, bytes touched ...)."""

    __slots__ = ("_tr", "name", "attrs", "trace_id", "span_id",
                 "parent_id", "_t0", "_c0", "_n0")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tr = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        tr = self._tr
        tls = tr._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        if stack:
            self.trace_id, self.parent_id = stack[-1]
        else:
            ctx = getattr(tls, "ctx", None)
            if ctx is not None:
                self.trace_id, self.parent_id = ctx
            else:
                self.trace_id, self.parent_id = tr.new_id(), 0
        self.span_id = tr.new_id()
        stack.append((self.trace_id, self.span_id))
        tally = getattr(tls, "tally", None)
        self._n0 = tally.copy() if tally else None
        self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        cpu = time.thread_time() - self._c0
        tr = self._tr
        tls = tr._tls
        # pop up to and including our own entry: an exception between an
        # explicitly paired enter/exit deeper in may have orphaned inner
        # entries, and a reused pool thread must not inherit them
        stack = tls.stack
        while stack:
            if stack.pop()[1] == self.span_id:
                break
        attrs = self.attrs
        if cpu:
            attrs["cpu"] = cpu
        tally = getattr(tls, "tally", None)
        if tally:
            n0 = self._n0 or {}
            for k, v in tally.items():
                if v != n0.get(k, 0):
                    attrs[k] = v - n0.get(k, 0)
        tr.record(Span(self.name, self.trace_id, self.span_id,
                       self.parent_id, self._t0, t1 - self._t0, tr.pid,
                       tr.native_id(), attrs))
        return False


class _Activate:
    """Adopt a remote (or otherwise explicit) trace context as this
    thread's root: spans opened on an empty stack parent under it instead
    of starting fresh traces.  A falsy trace id makes this a no-op, so
    callers can pass through unconditionally."""

    __slots__ = ("_tr", "_ctx", "_saved")

    def __init__(self, tracer: "Tracer", trace_id: int, parent_id: int):
        self._tr = tracer
        self._ctx = (trace_id, parent_id) if trace_id else None

    def __enter__(self):
        tls = self._tr._tls
        self._saved = getattr(tls, "ctx", None)
        if self._ctx is not None:
            tls.ctx = self._ctx
        return self

    def __exit__(self, *exc):
        self._tr._tls.ctx = self._saved
        return False


class Tracer:
    """Per-process span collector.  All public methods are thread-safe;
    ``enabled`` is a plain attribute read on the hot path.  ``dropped``
    counts the spans a full ring evicted before anyone drained them."""

    def __init__(self, capacity: int = 16384, pid: int | None = None):
        self.enabled = False
        self.capacity = int(capacity)
        self.pid = os.getpid() if pid is None else pid
        self._mu = threading.Lock()
        self._spans: deque[Span] = deque(maxlen=self.capacity)  # guarded-by: _mu
        self.dropped = 0  # guarded-by: _mu
        self._dropped_on = 0  # ``dropped`` when tracing was last enabled
        self._tls = threading.local()
        # ids unique across processes without coordination: a random
        # 32-bit per-process salt above a monotone counter
        self._salt = int.from_bytes(os.urandom(4), "big") | 1
        self._ids = itertools.count(1)

    def enable(self, on: bool = True) -> None:
        """Turn tracing on or off.  Turning it off logs a warning (on
        stderr by default) with the number of spans the full ring evicted
        while it was on, if any."""
        with self._mu:
            if on and not self.enabled:
                self._dropped_on = self.dropped
            lost = self.dropped - self._dropped_on
        if not on and self.enabled and lost:
            _log.warning("tracing: the full ring (%d spans) evicted %d spans "
                         "before they were drained", self.capacity, lost)
        self.enabled = on

    # -- id / context --------------------------------------------------------
    def new_id(self) -> int:
        return (self._salt << 32) | (next(self._ids) & 0xFFFFFFFF)

    def current(self) -> tuple[int, int]:
        """(trace_id, span_id) of the innermost open span on this thread,
        falling back to an ``activate``d context, else ``(0, 0)``."""
        stack = getattr(self._tls, "stack", None)
        if stack:
            return stack[-1]
        return getattr(self._tls, "ctx", None) or (0, 0)

    def activate(self, trace_id: int, parent_id: int) -> _Activate:
        return _Activate(self, int(trace_id), int(parent_id))

    def native_id(self) -> int:
        """``threading.get_native_id()``, read once a thread: it is a
        system call, which costs microseconds on some hosts."""
        tls = self._tls
        tid = getattr(tls, "tid", None)
        if tid is None:
            tid = tls.tid = threading.get_native_id()
        return tid

    # -- per-thread tallies --------------------------------------------------
    def count(self, name: str, n: int) -> None:
        """Add ``n`` to this thread's running tally ``name`` (only while
        tracing is enabled): every span open on the thread records the
        change as its attribute ``name``."""
        if not self.enabled:
            return
        tls = self._tls
        tally = getattr(tls, "tally", None)
        if tally is None:
            tally = tls.tally = {}
        tally[name] = tally.get(name, 0) + n

    # -- span creation -------------------------------------------------------
    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NOOP
        return _SpanCM(self, name, attrs)

    def start_span(self, name: str, **attrs) -> _SpanCM:
        """Open a span *without* pushing the thread stack — for intervals
        whose begin/end straddle threads (scatter-gather roots).  Close
        with ``finish()``; read ``trace_id``/``span_id`` for child ctx."""
        cm = _SpanCM(self, name, attrs)
        stack = getattr(self._tls, "stack", None)
        if stack:
            cm.trace_id, cm.parent_id = stack[-1]
        else:
            ctx = getattr(self._tls, "ctx", None)
            if ctx is not None:
                cm.trace_id, cm.parent_id = ctx
            else:
                cm.trace_id, cm.parent_id = self.new_id(), 0
        cm.span_id = self.new_id()
        cm._t0 = time.perf_counter()
        return cm

    def finish(self, cm: _SpanCM) -> None:
        """Record a ``start_span`` handle."""
        self.record(Span(cm.name, cm.trace_id, cm.span_id, cm.parent_id,
                         cm._t0, time.perf_counter() - cm._t0, self.pid,
                         self.native_id(), cm.attrs))

    # -- ring buffer ---------------------------------------------------------
    def record(self, span: Span) -> None:
        with self._mu:
            if len(self._spans) == self.capacity:
                self.dropped += 1
            self._spans.append(span)

    def spans(self) -> list[Span]:
        """Non-destructive snapshot of the ring (oldest first)."""
        with self._mu:
            return list(self._spans)

    def drain(self) -> list[Span]:
        with self._mu:
            out = list(self._spans)
            self._spans.clear()
        return out

    def clear(self) -> None:
        with self._mu:
            self._spans.clear()

    def take(self, trace_id: int) -> list[dict]:
        """Remove and return (as wire dicts) every ringed span of one
        trace — what a shard worker ships back with a query response."""
        with self._mu:
            keep, out = [], []
            for sp in self._spans:
                (out if sp.trace_id == trace_id else keep).append(sp)
            self._spans.clear()
            self._spans.extend(keep)
        return [sp.to_wire() for sp in out]

    def absorb(self, span_dicts: list[dict], pid: int | None = None,
               offset: float = 0.0) -> int:
        """Merge wire-form spans from another process into this ring,
        re-based onto this process's clock by ``offset`` (seconds to add
        to each ``t0``) and re-labelled with ``pid`` for display.  Ids are
        kept verbatim — the per-process salt guarantees no collisions, and
        parents minted router-side stay resolvable."""
        spans = [Span.from_wire(d) for d in span_dicts]
        for sp in spans:
            sp.t0 += offset
            if pid is not None:
                sp.pid = pid
        with self._mu:
            self.dropped += max(0, len(self._spans) + len(spans)
                                - self.capacity)
            self._spans.extend(spans)
        return len(spans)


#: process-wide default tracer; instrumentation goes through the module
#: helpers below so call sites stay one short name
TRACER = Tracer()


def span(name: str, **attrs):
    if not TRACER.enabled:
        return _NOOP
    return _SpanCM(TRACER, name, attrs)


def count(name: str, n: int) -> None:
    """``TRACER.count``; disabled cost is one attribute read."""
    if TRACER.enabled:
        TRACER.count(name, n)


def enable(on: bool = True) -> None:
    TRACER.enable(on)


# -- compiles -------------------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Process-wide count of the XLA programs JAX compiles or loads from
    its persistent compilation cache, and the seconds they take.

    Always on once ``install``ed (compiles are rare): JAX reports each
    program's compile-or-load as one ``COMPILE_EVENT`` time span, and a
    cache load also fires ``CACHE_HIT_EVENT`` on the same thread inside it.
    While ``tracer`` is enabled each is also recorded as a ``jax.compile``
    span (attributes ``fun`` and ``cache``: ``hit`` or ``miss``), parented
    under the span open on the compiling thread, on ``perf_counter``'s
    clock."""

    def __init__(self, tracer: Tracer):
        self._tr = tracer
        self._mu = threading.Lock()
        self._tls = threading.local()
        self._installed = False
        self.compiles = 0     # guarded-by: _mu
        self.cache_loads = 0  # guarded-by: _mu
        self.seconds = 0.0    # guarded-by: _mu
        # (end on perf_counter, seconds, fun, cache hit) of the newest
        self._log: deque[tuple] = deque(maxlen=4096)  # guarded-by: _mu

    def install(self) -> None:
        """Register with ``jax.monitoring`` (once; imports JAX)."""
        with self._mu:
            if self._installed:
                return
            self._installed = True
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_time_span_listener(self._on_span)

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._tls.hit = True

    def _on_span(self, event: str, start: float, end: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        hit = getattr(self._tls, "hit", False)
        self._tls.hit = False
        dur = end - start
        t0 = start + (time.perf_counter() - time.time())
        fun = str(kw.get("fun_name", "?"))
        with self._mu:
            if hit:
                self.cache_loads += 1
            else:
                self.compiles += 1
            self.seconds += dur
            self._log.append((t0 + dur, dur, fun, hit))
        tr = self._tr
        if tr.enabled:
            trace_id, parent = tr.current()
            tr.record(Span("jax.compile", trace_id or tr.new_id(),
                           tr.new_id(), parent, t0, dur, tr.pid,
                           tr.native_id(),
                           {"fun": fun, "cache": "hit" if hit else "miss"}))

    def snapshot(self) -> dict:
        """``compiles``, ``cache_loads`` and ``seconds`` so far."""
        with self._mu:
            return {"compiles": self.compiles,
                    "cache_loads": self.cache_loads,
                    "seconds": self.seconds}

    def events(self) -> list[dict]:
        """The newest compiles and loads, oldest first: ``t1`` (end, on
        ``perf_counter``), ``s`` (seconds), ``fun`` and ``cache``."""
        with self._mu:
            log = list(self._log)
        return [{"t1": t1, "s": s, "fun": fun, "cache": "hit" if hit
                 else "miss"} for t1, s, fun, hit in log]


#: the process's compile counter; the first program module that imports
#: JAX installs it
COMPILES = CompileCounter(TRACER)


# -- Chrome trace-event export ------------------------------------------------

def chrome_trace_events(spans: list[Span],
                        process_names: dict[int, str] | None = None,
                        base: float | None = None) -> list[dict]:
    """Spans -> Chrome trace-event dicts (complete events, microseconds
    relative to the earliest span).  Span/parent/trace ids ride in
    ``args`` so tooling can rebuild the tree; visual nesting in
    Perfetto/chrome://tracing comes from ts/dur containment per track."""
    if not spans:
        return []
    if base is None:
        base = min(sp.t0 for sp in spans)
    events = []
    for p in sorted({sp.pid for sp in spans}):
        name = (process_names or {}).get(p, f"pid {p}")
        events.append({"name": "process_name", "ph": "M", "pid": p,
                       "tid": 0, "args": {"name": name}})
    for sp in spans:
        events.append({
            "name": sp.name, "cat": "repro", "ph": "X",
            "ts": (sp.t0 - base) * 1e6, "dur": sp.dur * 1e6,
            "pid": sp.pid, "tid": sp.tid % (1 << 31),
            "args": {"trace": format(sp.trace_id, "x"),
                     "span": format(sp.span_id, "x"),
                     "parent": format(sp.parent_id, "x"),
                     **sp.attrs}})
    return events


def export_trace(path: str, tracer: Tracer | None = None,
                 process_names: dict[int, str] | None = None) -> int:
    """Write the tracer's ring (non-destructively) as Chrome trace-event
    JSON; returns the number of spans exported."""
    tr = tracer or TRACER
    spans = tr.spans()
    doc = {"traceEvents": chrome_trace_events(spans, process_names),
           "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(spans)
