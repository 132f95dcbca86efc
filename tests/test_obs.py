"""repro.obs: trace spans, mergeable histograms, drift detection."""

import json
import threading

import numpy as np
import pytest

from repro.analytics.query import QueryResult, StageStats
from repro.core.knobs import FidelityOption, IngestSpec
from repro.launch.vserve import demo_config
from repro.obs import (DriftDetector, Histogram, MetricsRegistry,
                       Span, Tracer, merge_reports)
from repro.obs import trace as obstrace


def _tracer(**kw):
    tr = Tracer(**kw)
    tr.enabled = True
    return tr


# -- span facility ------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    tr = Tracer()
    assert tr.enabled is False
    cm = tr.span("x", bytes=1)
    assert cm is obstrace._NOOP
    with cm as sp:
        sp.set(more=2)  # no-op, no error
    assert tr.spans() == []
    # the module-level helper takes the same fast path
    assert obstrace.TRACER.enabled is False
    assert obstrace.span("y") is obstrace._NOOP


def test_nesting_parents_and_attrs():
    tr = _tracer(pid=7)
    with tr.span("outer", key="a") as outer:
        with tr.span("inner") as inner:
            inner.set(bytes=42)
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # exit order
    si, so = spans
    assert si.trace_id == so.trace_id
    assert si.parent_id == so.span_id
    assert so.parent_id == 0
    # beside its own attributes a span records its thread's CPU seconds
    assert si.attrs.pop("cpu", 0.0) >= 0.0
    assert so.attrs.pop("cpu", 0.0) >= 0.0
    assert si.attrs == {"bytes": 42}
    assert so.attrs == {"key": "a"}
    assert si.pid == 7 and si.dur >= 0.0


def test_siblings_share_trace():
    tr = _tracer()
    with tr.span("root") as root:
        with tr.span("a"):
            pass
        with tr.span("b"):
            pass
    a, b, r = tr.spans()
    assert a.parent_id == b.parent_id == root.span_id
    assert a.trace_id == b.trace_id == r.trace_id


def test_ring_is_bounded():
    tr = _tracer(capacity=8)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    got = tr.spans()
    assert len(got) == 8
    assert got[-1].name == "s49"  # newest survive


def test_thread_stacks_isolated():
    tr = _tracer()
    seen = {}

    def work(label):
        with tr.span(f"root-{label}"):
            with tr.span(f"leaf-{label}") as leaf:
                seen[label] = leaf.trace_id

    ts = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(set(seen.values())) == 4  # each thread its own trace
    by_name = {s.name: s for s in tr.spans()}
    for i in range(4):
        assert (by_name[f"leaf-{i}"].parent_id
                == by_name[f"root-{i}"].span_id)


def test_activate_adopts_remote_context():
    tr = _tracer()
    with tr.activate(111, 222):
        assert tr.current() == (111, 222)
        with tr.span("child"):
            pass
    child = tr.spans()[0]
    assert child.trace_id == 111 and child.parent_id == 222
    # falsy trace id: no adoption, spans start fresh traces
    with tr.activate(0, 0):
        with tr.span("fresh"):
            pass
    fresh = tr.spans()[-1]
    assert fresh.trace_id != 111 and fresh.parent_id == 0


def test_exception_unwinds_span_stack():
    tr = _tracer()
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise RuntimeError("boom")
    # both spans recorded, stack fully unwound
    assert [s.name for s in tr.spans()] == ["inner", "outer"]
    assert getattr(tr._tls, "stack", []) == []


def test_orphaned_manual_enter_heals():
    # a stage body that raises between explicit __enter__/__exit__ pairs
    # (executor style) must not leak stack entries into a reused thread
    tr = _tracer()
    outer = tr.span("outer")
    outer.__enter__()
    tr.span("leaked").__enter__()  # never exited
    outer.__exit__(None, None, None)
    assert getattr(tr._tls, "stack") == []
    with tr.span("next"):
        pass
    assert tr.spans()[-1].parent_id == 0  # not parented under leftovers


def test_take_removes_single_trace():
    tr = _tracer()
    with tr.activate(5, 0):
        with tr.span("mine"):
            pass
    with tr.span("other"):
        pass
    out = tr.take(5)
    assert [d["n"] for d in out] == ["mine"]
    assert [s.name for s in tr.spans()] == ["other"]
    assert tr.take(5) == []


def test_wire_roundtrip_through_cluster_pack():
    from repro.cluster.wire import pack, unpack
    tr = _tracer(pid=3)
    with tr.span("s", cf="cf_x", bytes=12345, arr=(1, 2)):
        pass
    sp = tr.spans()[0]
    d = unpack(pack(sp.to_wire()))
    back = Span.from_wire(d)
    assert (back.trace_id, back.span_id, back.parent_id) == \
        (sp.trace_id, sp.span_id, sp.parent_id)  # 64-bit ids survive
    assert back.name == "s" and back.pid == 3
    assert back.attrs["bytes"] == 12345
    assert back.attrs["arr"] == "(1, 2)"  # non-scalars coerced to str


def test_absorb_rebases_clock_and_pid():
    remote = _tracer(pid=99)
    with remote.span("remote-work"):
        pass
    wire = [s.to_wire() for s in remote.drain()]
    t0_remote = wire[0]["t0"]
    local = _tracer(pid=0)
    n = local.absorb(wire, pid=2, offset=10.0)
    assert n == 1
    sp = local.spans()[0]
    assert sp.pid == 2
    assert sp.t0 == pytest.approx(t0_remote + 10.0)
    assert sp.span_id == wire[0]["s"]  # ids kept verbatim


def test_cross_process_ids_do_not_collide():
    a, b = Tracer(), Tracer()
    ids = {a.new_id() for _ in range(1000)} | {b.new_id()
                                              for _ in range(1000)}
    assert len(ids) == 2000


def test_chrome_export_structure(tmp_path):
    tr = _tracer(pid=1)
    with tr.span("parent"):
        with tr.span("child", bytes=7):
            pass
    path = tmp_path / "trace.json"
    n = obstrace.export_trace(str(path), tracer=tr,
                              process_names={1: "worker"})
    assert n == 2
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "worker"
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"parent", "child"}
    assert xs["child"]["args"]["parent"] == xs["parent"]["args"]["span"]
    assert xs["child"]["args"]["bytes"] == 7
    assert xs["parent"]["ts"] >= 0 and xs["parent"]["dur"] > 0
    # export is non-destructive
    assert len(tr.spans()) == 2


# -- histograms ---------------------------------------------------------------

def test_histogram_percentiles_basic():
    h = Histogram()
    for _ in range(100):
        h.observe(0.01)
    s = h.snapshot()
    assert s["count"] == 100
    assert s["mean"] == pytest.approx(0.01)
    assert s["p50"] == pytest.approx(0.01, rel=0.5)
    assert s["min"] == s["max"] == pytest.approx(0.01)


def test_histogram_merge_skewed_shards_p95():
    # satellite regression: two shards with wildly different latency
    # distributions must roll up to the p95 of the UNION, not an average
    # of the per-shard p95s
    fast, slow = Histogram(), Histogram()
    for _ in range(150):
        fast.observe(0.001)
    for _ in range(50):
        slow.observe(0.4)
    merged = Histogram.merge([fast.snapshot(), slow.snapshot()])
    assert merged["count"] == 200
    # 75% of samples at 1ms -> p50 stays fast
    assert merged["p50"] == pytest.approx(0.001, rel=0.6)
    # p95 lands in the slow shard's bucket (0.2, 0.5]; averaging the two
    # per-shard p95s (~0.001 and ~0.4) would misreport ~0.2
    assert 0.25 <= merged["p95"] <= 0.5
    assert merged["max"] == pytest.approx(0.4)
    assert merged["sum"] == pytest.approx(150 * 0.001 + 50 * 0.4)


def test_histogram_merge_rejects_mismatched_bounds():
    a = Histogram()
    b = Histogram(bounds=(0.1, 1.0))
    a.observe(0.2)
    b.observe(0.2)
    with pytest.raises(ValueError):
        Histogram.merge([a.snapshot(), b.snapshot()])


def test_histogram_merge_empty_and_none():
    merged = Histogram.merge([])
    assert merged["count"] == 0 and merged["p95"] == 0.0
    h = Histogram()
    h.observe(0.05)
    merged = Histogram.merge([None, {}, h.snapshot()])
    assert merged["count"] == 1


def test_metrics_registry():
    m = MetricsRegistry()
    m.inc("queries")
    m.inc("queries", 2)
    m.inc("video_seconds", 1.5)
    m.set_gauge("inflight", 3)
    m.observe("latency_s", 0.02)
    snap = m.snapshot()
    assert snap["counters"]["queries"] == 3
    assert snap["counters"]["video_seconds"] == pytest.approx(1.5)
    assert snap["gauges"]["inflight"] == 3
    assert snap["histograms"]["latency_s"]["count"] == 1
    assert m.value("queries") == 3


# -- drift detection ----------------------------------------------------------

def _result(op, sf_id, cf, segments, consume_s, retrieve_s=0.0):
    st = StageStats(op=op, cf=cf, sf_id=sf_id)
    st.segments_scanned = segments
    st.consume_s = consume_s
    st.retrieve_s = retrieve_s
    return QueryResult(items=set(), stages=[st],
                       video_seconds=segments * 4.0, wall_s=consume_s)


def test_drift_detector_flags_slow_consumption():
    cfg = demo_config()
    spec = IngestSpec()
    det = DriftDetector(cfg, spec, tolerance=3.0)
    plan = cfg.plans[0]
    op, acc, cf = plan.consumer.op, plan.consumer.target, plan.cf
    sf_id = cfg.node_id(0)
    # observed at the expected speed: no drift
    ok_consume = 10 * spec.segment_seconds / plan.speed
    det.observe(acc, _result(op, sf_id, cf, 10, ok_consume))
    rep = det.report()
    knob = f"{op}@{acc:g}"
    assert rep["consumption"][knob]["drifted"] is False
    assert rep["drifted"] is False
    # now 10x slower than profiled, repeatedly (EMA converges past 1/3)
    for _ in range(20):
        det.observe(acc, _result(op, sf_id, cf, 10, 10 * ok_consume))
    rep = det.report()
    assert rep["consumption"][knob]["drifted"] is True
    assert rep["consumption"][knob]["ratio"] < 1 / 3
    assert rep["drifted"] is True


def test_drift_retrieval_slow_only():
    cfg = demo_config()
    spec = IngestSpec()
    plan = cfg.plans[0]
    sf_id = cfg.node_id(0)
    det = DriftDetector(cfg, spec,
                        retrieval_speeds={(sf_id, plan.cf.name()): 100.0},
                        tolerance=3.0)
    acc, cf, op = plan.consumer.target, plan.cf, plan.consumer.op
    # retrieval far FASTER than profiled (cache hits): not drift
    for _ in range(20):
        det.observe(acc, _result(op, sf_id, cf, 10,
                                 consume_s=0.0,
                                 retrieve_s=10 * spec.segment_seconds
                                 / 10000.0))
    key = f"{sf_id}:{plan.cf.name()}"
    assert det.report()["retrieval"][key]["drifted"] is False
    # far slower: drift
    det2 = DriftDetector(cfg, spec,
                         retrieval_speeds={(sf_id, plan.cf.name()): 100.0},
                         tolerance=3.0)
    for _ in range(20):
        det2.observe(acc, _result(op, sf_id, cf, 10,
                                  consume_s=0.0,
                                  retrieve_s=10 * spec.segment_seconds
                                  / 2.0))
    assert det2.report()["retrieval"][key]["drifted"] is True


def test_merge_reports_keeps_worst_shard():
    row_ok = {"expected_x": 100.0, "observed_x": 90.0, "ratio": 0.9,
              "samples": 5, "drifted": False}
    row_bad = {"expected_x": 100.0, "observed_x": 10.0, "ratio": 0.1,
               "samples": 5, "drifted": True}
    merged = merge_reports([
        {"consumption": {"nn@0.9": row_ok}, "retrieval": {},
         "drifted": False},
        {"consumption": {"nn@0.9": row_bad}, "retrieval": {},
         "drifted": True},
        {},  # a shard with no observations yet
    ])
    assert merged["consumption"]["nn@0.9"]["ratio"] == 0.1
    assert merged["drifted"] is True


def test_invalid_tolerance_rejected():
    with pytest.raises(ValueError):
        DriftDetector(demo_config(), IngestSpec(), tolerance=1.0)


# -- request trace context ----------------------------------------------------

def test_query_request_trace_fields_roundtrip():
    from repro.cluster.wire import pack, unpack
    from repro.serving.server import QueryRequest
    req = QueryRequest("A", "jackson", [0, 1], 0.9,
                       trace_id=(7 << 32) | 1, parent_span=(7 << 32) | 2)
    back = QueryRequest.from_wire(unpack(pack(req.to_wire())))
    assert back.trace_id == req.trace_id
    assert back.parent_span == req.parent_span
    # old-style frames without trace fields default to "no context"
    legacy = {"query": "A", "stream": "s", "segments": [0],
              "accuracy": 0.8}
    assert QueryRequest.from_wire(legacy).trace_id == 0


# -- per-thread tallies, evictions, compiles ------------------------------------

@pytest.fixture
def traced():
    """The process tracer, enabled and empty; restored afterwards."""
    obstrace.TRACER.drain()
    obstrace.enable(True)
    try:
        yield obstrace.TRACER
    finally:
        obstrace.enable(False)
        obstrace.TRACER.drain()


def tally(tr):
    """This thread's running tallies in ``tr``."""
    return dict(getattr(tr._tls, "tally", None) or {})


def test_tallies_nest_into_parents():
    tr = _tracer()
    with tr.span("outer"):
        tr.count("h2d_bytes", 10)
        with tr.span("a"):
            tr.count("h2d_bytes", 100)
            sum(range(20000))
        with tr.span("b"):
            tr.count("h2d_bytes", 1000)
            tr.count("d2h_bytes", 7)
            sum(range(20000))
    by = {s.name: s.attrs for s in tr.spans()}
    assert by["a"]["h2d_bytes"] == 100 and "d2h_bytes" not in by["a"]
    assert by["b"]["h2d_bytes"] == 1000 and by["b"]["d2h_bytes"] == 7
    assert by["outer"]["h2d_bytes"] == 1110 >= (by["a"]["h2d_bytes"]
                                                + by["b"]["h2d_bytes"])
    assert by["outer"]["d2h_bytes"] == 7
    assert by["outer"]["cpu"] >= by["a"]["cpu"] + by["b"]["cpu"] > 0
    assert tally(tr) == {"h2d_bytes": 1110, "d2h_bytes": 7}


def test_tallies_are_per_thread():
    tr = _tracer()
    done = threading.Event()

    def other():
        tr.count("h2d_bytes", 5)
        done.set()
    with tr.span("mine"):
        th = threading.Thread(target=other)
        th.start()
        th.join()
    assert done.is_set()
    assert "h2d_bytes" not in tr.spans()[0].attrs
    assert tally(tr) == {}


def test_disabled_count_leaves_tallies_unchanged():
    tr = _tracer()
    tr.count("h2d_bytes", 3)
    before = tally(tr)
    tr.enabled = False
    assert tr.span("x") is obstrace._NOOP
    tr.count("h2d_bytes", 1 << 20)
    tr.count("d2h_bytes", 1 << 20)
    assert tally(tr) == before == {"h2d_bytes": 3}
    # the module helper: one attribute read, nothing counted
    assert obstrace.TRACER.enabled is False
    glob = tally(obstrace.TRACER)
    assert obstrace.span("y") is obstrace._NOOP
    obstrace.count("h2d_bytes", 99)
    assert tally(obstrace.TRACER) == glob


def test_wire_frames_are_counted_while_tracing(traced):
    import socket

    from repro.cluster import wire
    a, b = socket.socketpair()
    try:
        msg = {"op": "query", "x": list(range(50))}
        n = 4 + len(wire.pack(msg))
        base = tally(traced)
        with traced.span("rpc:query"):
            wire.send_msg(a, msg)
            assert wire.recv_msg(b) == msg
        sp, = traced.spans()
        assert sp.attrs["wire_tx_bytes"] == sp.attrs["wire_rx_bytes"] == n
        traced.enable(False)
        wire.send_msg(a, msg)
        assert wire.recv_msg(b) == msg
        got = tally(traced)
        moved = {k: got[k] - base.get(k, 0) for k in got}
        assert {k: v for k, v in moved.items() if v} == {
            "wire_tx_bytes": n, "wire_rx_bytes": n}
    finally:
        a.close()
        b.close()


def test_ring_counts_evictions():
    tr = _tracer(capacity=8)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped == 42
    tr.absorb([Span("r", 1, 2, 0, 0.0, 1.0, 1, 1, {}).to_wire()] * 3)
    assert tr.dropped == 45 and len(tr.spans()) == 8


def test_disable_reports_evictions(traced, caplog):
    for _ in range(traced.capacity + 5):
        with obstrace.span("flood"):
            pass
    with caplog.at_level("WARNING", logger=obstrace.__name__):
        obstrace.enable(False)
    assert "evicted 5 spans" in caplog.text


def test_spans_carry_native_thread_id():
    tr = _tracer()
    with tr.span("x"):
        pass
    cm = tr.start_span("y")
    tr.finish(cm)
    assert {s.tid for s in tr.spans()} == {threading.get_native_id()}


def test_fresh_jit_records_one_compile_span(traced):
    import jax

    import repro.codec.transform  # noqa: F401 - installs the counter

    def fresh_probe(x):
        return x * 3 + 1
    before = obstrace.COMPILES.snapshot()
    with obstrace.span("outer") as outer:
        jax.jit(fresh_probe)(np.ones((7, 13), np.float32)).block_until_ready()
    comp = [s for s in traced.spans() if s.name == "jax.compile"]
    assert len(comp) == 1, [s.attrs for s in comp]
    sp = comp[0]
    assert "fresh_probe" in sp.attrs["fun"]
    assert sp.attrs["cache"] in ("hit", "miss")
    assert sp.parent_id == outer.span_id
    after = obstrace.COMPILES.snapshot()
    assert (after["compiles"] + after["cache_loads"]
            == before["compiles"] + before["cache_loads"] + 1)
    assert after["seconds"] - before["seconds"] == pytest.approx(sp.dur)
    # on the tracer's clock: inside the span it ran under
    out = [s for s in traced.spans() if s.name == "outer"][0]
    assert out.t0 - 0.05 <= sp.t0 and sp.t0 + sp.dur <= out.t0 + out.dur + 0.05
    ev = obstrace.COMPILES.events()[-1]
    assert ev["fun"] == sp.attrs["fun"] and ev["s"] == sp.dur


# -- the data path's spans and crossings at the demo grid -------------------------

def _golden_ingest(tmp_path):
    from repro.analytics.scene import generate_segment
    from repro.ingest import IngestScheduler
    from repro.videostore import VideoStore
    spec = IngestSpec()
    cfg = demo_config()
    vs = VideoStore(str(tmp_path / "vs"), spec)
    vs.set_formats(cfg.storage_formats())
    sched = IngestScheduler(vs, cfg)
    frames = [generate_segment("jackson", s, spec)[0] for s in range(3)]
    return spec, cfg, vs, sched, frames


def test_golden_encode_spans_and_bytes(tmp_path, traced):
    spec, cfg, vs, sched, frames = _golden_ingest(tmp_path)
    sched.ingest("jackson", 0, frames[0])
    spans = traced.spans()
    golden = [s for s in spans if s.name == "ingest.golden"]
    assert len(golden) == 1
    g = golden[0]
    kids = {s.name: s for s in spans if s.parent_id == g.span_id}
    assert {"encode.convert", "codec.transform", "codec.compress"} <= set(kids)
    n, h, w = frames[0].shape
    px = n * h * w
    # uint8 frames up and down for the conversion; float32 chunks up and
    # int16 symbols down for the transform; zstd stays on the host
    assert kids["encode.convert"].attrs["h2d_bytes"] == px
    assert kids["encode.convert"].attrs["d2h_bytes"] == px
    assert kids["codec.transform"].attrs["h2d_bytes"] == 4 * px
    assert kids["codec.transform"].attrs["d2h_bytes"] == 2 * px
    assert "h2d_bytes" not in kids["codec.compress"].attrs
    assert g.attrs["h2d_bytes"] == 5 * px and g.attrs["d2h_bytes"] == 3 * px
    for s in kids.values():
        assert 0.0 < s.attrs["cpu"] and s.t0 >= g.t0


def test_decode_convert_detect_bytes(tmp_path, traced):
    from repro.analytics import operators as ops
    from repro.codec.segment import _pad_chunk_count
    spec, cfg, vs, sched, frames = _golden_ingest(tmp_path)
    for seg in range(3):
        sched.ingest("jackson", seg, frames[seg])
    golden = [sid for sid, sf in vs.formats.items() if not sf.coding.bypass][0]
    n, h, w = frames[0].shape
    traced.drain()
    want = np.arange(n)
    out, _ = vs.decode_many_for("jackson", [0, 1, 2], golden, want)
    dec = [s for s in traced.drain() if s.name == "codec.decode"][0]
    k = min(vs.formats[golden].coding.keyframe, n)
    chunks = _pad_chunk_count(3 * -(-n // k))
    # int16 symbols of every (padded) chunk up, uint8 frames down
    assert dec.attrs["h2d_bytes"] == chunks * k * h * w * 2
    assert dec.attrs["d2h_bytes"] == chunks * k * h * w
    cf = FidelityOption("good", 1.0, 270, 1 / 2)
    n2, h2, w2 = spec.resolve(cf)
    conv = vs.convert(out[0][:n2], golden, cf)
    sp = [s for s in traced.drain() if s.name == "convert"][0]
    assert conv.shape == (n2, h2, w2)
    assert sp.attrs["h2d_bytes"] == n2 * h * w * 4   # float32 frames up
    assert sp.attrs["d2h_bytes"] == n2 * h2 * w2     # uint8 frames down
    with obstrace.span("detect") as det:
        ops.Diff().detect(conv, cf, spec)
    with obstrace.span("detect.motion"):
        ops.Motion().detect(conv, cf, spec)
    got = {s.name: s.attrs for s in traced.drain()}
    assert det.attrs["h2d_bytes"] == n2 * h2 * w2 * 4
    assert got["detect"]["d2h_bytes"] == (n2 - 1) * 4   # float32 scores
    ty, tx = ops.Motion.grid
    assert got["detect.motion"]["h2d_bytes"] == n2 * h2 * w2 * 4
    assert got["detect.motion"]["d2h_bytes"] == n2 * ty * tx * 4
