"""The readers of the host layers' tallies and compiles: on hand-made
spans, silent on spans of a program without the tallies (the slice of
``data/trace_sample.json``), and on a slice recorded on a TPU v5e with
them (``data/host_sample.json``: spans of a traced ``2sf720-ingest`` run
and of a traced ``golden720-scan`` run at 720p, cut to whole trees)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import hostlayers as hl  # noqa: E402

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = H.load_benchmark()
NEW = ("encode_convert_ms_per_seg", "encode_transform_ms_per_seg",
       "encode_compress_ms_per_seg", "golden_h2d_bytes_per_seg",
       "decode_h2d_bytes_per_seg", "convert_h2d_bytes_per_frame",
       "entropy_cpu_pct", "convert_cpu_pct", "window_compiles.query",
       "window_compiles.hot", "window_compiles.ingest", "setup_compile_s")
# 720p30, 4 s segments: 120 frames of 720 x 1280; RAW 360p half rate
PX720, PX360 = 720 * 1280, 360 * 640


def span(name, sid, parent, t0, d, **attrs):
    return {"n": name, "s": sid, "p": parent, "t": 1, "t0": t0, "d": d,
            "a": attrs}


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def run_of(workload, spans, records=()):
    cell = {w["name"]: w for w in BENCH["workloads"]}[workload]
    return H.Run(workload, H.load_config(cell["config"]), {}, "TPU v5 lite",
                 records=list(records), spans=spans)


def hand_ingest():
    return [span("ingest.golden", 1, 0, 0.0, 10.0, h2d_bytes=50, cpu=4.0),
            span("encode.convert", 2, 1, 0.5, 1.0, h2d_bytes=10),
            span("codec.transform", 3, 1, 2.0, 4.0, h2d_bytes=40),
            span("jax.compile", 9, 3, 2.5, 1.0, fun="jit_x", cache="miss"),
            span("codec.compress", 4, 1, 6.5, 3.0),
            span("ingest.transcode", 5, 0, 20.0, 2.0),
            span("encode.convert", 6, 5, 20.5, 0.5),
            span("ingest.golden", 7, 0, 30.0, 6.0, h2d_bytes=70, cpu=1.0),
            span("encode.convert", 8, 7, 30.0, 2.0)]


def test_encode_split_counts_only_spans_under_golden():
    sp = hand_ingest()
    # 1.0 and 2.0 ms-scale seconds under the two golden spans; the
    # transcode's conversion is not the golden encode's
    assert hl.self_ms_under(sp, "encode.convert", "ingest.golden") == \
        pytest.approx(1e3 * (1.0 + 2.0) / 2)
    # a compile inside the transform is not the transform's own time
    assert hl.self_ms_under(sp, "codec.transform", "ingest.golden") == \
        pytest.approx(3e3)
    assert hl.self_ms_under(sp, "codec.compress", "ingest.golden") == \
        pytest.approx(3e3)
    assert hl.self_ms_under(sp, "codec.compress", "ingest.transcode") is None
    # the golden span's own self time falls by its new children's
    run = run_of("2sf720-ingest", sp)
    assert H.metric_reader("golden_ms_per_seg")(run) == pytest.approx(
        1e3 * ((10.0 - 1.0 - 4.0 - 3.0) + (6.0 - 2.0)) / 2)


def test_bytes_and_cpu_share():
    sp = hand_ingest() + [
        span("codec.decode", 10, 0, 40.0, 1.0, segments=2, h2d_bytes=400),
        span("codec.decode", 11, 0, 42.0, 1.0, h2d_bytes=100),
        span("convert", 12, 0, 44.0, 2.0, frames=10, h2d_bytes=30, cpu=0.5),
        span("convert", 13, 0, 47.0, 2.0, frames=20, cpu=1.5)]
    assert hl.attr_per_unit(sp, "ingest.golden", "h2d_bytes") == 60
    assert hl.attr_per_unit(sp, "codec.decode", "h2d_bytes",
                            "segments") == pytest.approx(500 / 3)
    assert hl.attr_per_unit(sp, "convert", "h2d_bytes", "frames") == 1.0
    assert hl.attr_per_unit(sp, "codec.transform", "d2h_bytes") is None
    assert hl.cpu_pct(sp, "convert") == pytest.approx(50.0)
    assert hl.cpu_pct(sp, "ingest.golden") == pytest.approx(100 * 5 / 16)
    assert hl.cpu_pct(sp, "codec.decode") is None
    assert hl.window_compiles(sp) == 1
    ev = [{"t1": 1.0, "s": 0.5, "fun": "a", "cache": "miss"},
          {"t1": 5.0, "s": 2.0, "fun": "b", "cache": "hit"},
          {"t1": 9.0, "s": 4.0, "fun": "c", "cache": "miss"}]
    assert hl.compile_s_before(ev, 5.0) == pytest.approx(2.5)
    assert hl.compile_s_before(ev, 0.5) == 0


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_has_a_reader_and_cells(name):
    m = {x["name"]: x for x in BENCH["per_layer"]}[name]
    assert m["workloads"] and callable(H.metric_reader(name))
    for w in m["workloads"]:
        reports = {e["name"] for e in H.cell_metrics(BENCH, w, "end_to_end")}
        assert m["moves"] in reports


@pytest.mark.parametrize("name", NEW)
def test_silent_on_a_program_without_tallies(name, monkeypatch):
    """The older slice was recorded from a program whose spans carry no
    tallies and which had no compile counter: every new reader gives
    None there, and raises nothing."""
    monkeypatch.setattr(hl, "compile_counter", lambda: None)
    old = load("trace_sample.json")
    for w in ("golden720-scan", "2sf720-ingest"):
        run = run_of(w, old["spans"], [{"due": 0.0}])
        assert H.metric_reader(name)(run) is None


def recorded_run(part):
    rec = load("host_sample.json")
    part = rec[part]
    return rec["grid"], part, run_of(part["workload"], part["spans"])


def test_recorded_ingest_bytes_are_the_shapes():
    g, rec, run = recorded_run("ingest")
    px = g["frames"] * g["h"] * g["w"]
    # uint8 frames up for the conversion, float32 chunks up for the
    # forward transform: 1 + 4 bytes a pixel of a segment's frames
    assert H.metric_reader("golden_h2d_bytes_per_seg")(run) == 5 * px
    golden = {sp["s"] for sp in run.spans if sp["n"] == "ingest.golden"}
    seen = set()
    for sp in run.spans:
        if sp["p"] not in golden:
            continue
        seen.add(sp["n"])
        a = sp["a"]
        if sp["n"] == "codec.transform":
            assert (a["h2d_bytes"], a["d2h_bytes"]) == (4 * px, 2 * px)
        elif sp["n"] == "encode.convert":
            assert (a["h2d_bytes"], a["d2h_bytes"]) == (px, px)
        else:
            assert "h2d_bytes" not in a and "d2h_bytes" not in a
    assert {"encode.convert", "codec.transform", "codec.compress"} <= seen
    assert rec["expect"]["golden_h2d_bytes_per_seg"] == 5 * px


def test_recorded_encode_split_adds_up():
    _g, rec, run = recorded_run("ingest")
    names = ("golden_ms_per_seg", "encode_convert_ms_per_seg",
             "encode_transform_ms_per_seg", "encode_compress_ms_per_seg")
    parts = [H.metric_reader(n)(run) for n in names]
    golden = [sp for sp in run.spans if sp["n"] == "ingest.golden"]
    # the golden span's self time and its three children's make up the
    # golden span: nothing else runs under it on its thread
    assert all(p is not None and p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(
        1e3 * sum(sp["d"] for sp in golden) / len(golden), rel=1e-6)
    for n, v in zip(names, parts):
        assert v == pytest.approx(rec["expect"][n])
    assert H.metric_reader("window_compiles.ingest")(run) == 0


def test_recorded_scan_bytes_and_cpu():
    g, rec, run = recorded_run("scan")
    chunk = g["frames"] * g["h"] * g["w"]
    # int16 symbols of one padded chunk a segment up
    assert H.metric_reader("decode_h2d_bytes_per_seg")(run) == 2 * chunk
    # decoded golden frames up as float32
    assert H.metric_reader("convert_h2d_bytes_per_frame")(run) == \
        4 * g["h"] * g["w"]
    ent = [sp for sp in run.spans if sp["n"] == "codec.entropy"]
    assert H.metric_reader("entropy_cpu_pct")(run) == pytest.approx(
        100 * sum(sp["a"]["cpu"] for sp in ent) / sum(sp["d"] for sp in ent))
    for name in ("entropy_cpu_pct", "convert_cpu_pct",
                 "decode_h2d_bytes_per_seg", "convert_h2d_bytes_per_frame"):
        v = H.metric_reader(name)(run)
        assert v == pytest.approx(rec["expect"][name])
    assert 0.0 < H.metric_reader("convert_cpu_pct")(run) <= 100.0
    assert H.metric_reader("window_compiles.query")(run) == 0


def test_recorded_convert_sends_its_storage_grid_as_float32():
    g, rec, run = recorded_run("scan_raw")
    grid = {"sf_g": g["h"] * g["w"], "sf1": g["raw_h"] * g["raw_w"]}
    conv = [sp for sp in run.spans if sp["n"] == "convert"]
    assert conv
    for sp in conv:
        assert sp["a"]["h2d_bytes"] == 4 * grid[sp["a"]["sf"]] * \
            sp["a"]["frames"]
    assert H.metric_reader("convert_h2d_bytes_per_frame")(run) == \
        pytest.approx(rec["expect"]["convert_h2d_bytes_per_frame"])


def test_recorded_setup_compiles(monkeypatch):
    rec = load("host_sample.json")["ingest"]

    class Counter:
        def events(self):
            return rec["compile_events"]
    monkeypatch.setattr(hl, "compile_counter", Counter)
    run = run_of(rec["workload"], rec["spans"],
                 [{"due": rec["window_start"] + 1.0},
                  {"due": rec["window_start"]}])
    got = H.metric_reader("setup_compile_s")(run)
    assert got > 0 and got == pytest.approx(rec["expect"]["setup_compile_s"])
    assert got == pytest.approx(sum(
        e["s"] for e in rec["compile_events"]
        if e["t1"] <= rec["window_start"]))
