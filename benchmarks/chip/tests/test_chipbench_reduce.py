"""The reduction from spans and device traces to per-layer numbers, on a
small hand-made sample and on a slice recorded on a TPU v5e
(``data/trace_sample.json``: a traced run's spans and device programs,
cut to 1.5 s of its slice)."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import roofline  # noqa: E402
from benchmarks.chip import tracereduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_sample.json")


def span(name, sid, parent, t0, d, **attrs):
    return {"n": name, "s": sid, "p": parent, "t": 1, "t0": t0, "d": d,
            "a": attrs}


def test_self_time_leaves_out_children_once():
    spans = [span("retrieve", 1, 0, 0.0, 1.0),
             span("codec.entropy", 2, 1, 0.1, 0.3, segments=2),
             span("convert", 3, 1, 0.3, 0.4),      # overlaps entropy
             span("convert", 4, 0, 2.0, 0.5)]
    assert tr.self_times(spans, "retrieve") == [pytest.approx(0.4)]
    assert tr.per_unit_ms(spans, "convert") == pytest.approx(450.0)
    assert tr.per_unit_ms(spans, "codec.entropy", "segments") == \
        pytest.approx(150.0)
    assert tr.per_unit_ms(spans, "detect") is None


def test_percentile_is_nearest_rank():
    xs = list(range(1, 21))
    assert tr.percentile(xs, 95) == 19
    assert tr.percentile(xs, 50) == 10
    assert tr.percentile([3.0], 95) == 3.0


def hand_trace():
    mods = [["jit__chunk_residuals(1)", 100.0, 50.0],
            ["jit__residuals_scan(2)", 150.0, 10.0],
            ["jit__resize(3)", 140.0, 30.0],        # overlaps both
            ["jit__chunk_residuals(1)", 900.0, 200.0]]  # past the window
    return {"chips": [{"plane": "/device:TPU:0", "modules": mods}],
            "anchor_ns": 0.0, "anchor_t": 10.0, "t0_ns": 0.0,
            "t1_ns": 1000.0}


def test_busy_idle_and_programs():
    t = hand_trace()
    # busy: [100, 170] and [900, 1000] -> 170 ns of 1000
    assert tr.busy_s(t) == pytest.approx(170e-9)
    assert tr.idle_pct(t) == pytest.approx(83.0)
    runs = tr.module_runs(t, ("_chunk_residuals", "_residuals_scan"))
    assert [r[0] for r in runs] == ["jit__chunk_residuals(1)",
                                    "jit__residuals_scan(2)",
                                    "jit__chunk_residuals(1)"]
    top = tr.top_modules(t, 2)
    assert top[0][0] == "jit__chunk_residuals"
    assert top[0][1] == pytest.approx(150e-9)


def test_idle_gaps_are_named_by_the_host():
    t = hand_trace()
    spans = [span("store.get", 1, 0, 10.0 + 500e-9, 300e-9),
             span("query", 2, 0, 10.0, 1e-6)]
    gaps = tr.idle_gaps(t, spans, 3)
    assert gaps[0][0] == "store.get"        # [170, 900]: middle 535 ns
    assert gaps[0][1] == pytest.approx(730e-9)
    assert gaps[1][0] == "query"            # [0, 100]
    assert len(gaps) == 2


def test_decode_roofline_bound():
    nbytes, flops = roofline.residual_decode_work(120, 720, 1280)
    least, bound = roofline.least_time_s(nbytes, flops, "TPU v5 lite")
    assert bound == "bytes"
    assert least == pytest.approx(120 * 720 * 1280 * 3 / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def recorded():
    with open(DATA) as f:
        return json.load(f)


def test_recorded_slice_reads_sanely():
    rec = recorded()
    run = H.Run("golden720-scan", H.load_config("vstore720-1toN"), {},
                rec["device_kind"], spans=rec["spans"], trace=rec["trace"])
    idle = H.metric_reader("device_idle_pct.query")(run)
    assert 0.0 < idle < 100.0
    roof = H.metric_reader("decode_roofline_pct")(run)
    assert 0.0 < roof <= 100.0
    for name in ("entropy_ms_per_seg", "store_get_ms_per_seg",
                 "convert_ms_per_seg"):
        v = H.metric_reader(name)(run)
        assert v is not None and v > 0 and math.isfinite(v)
    # no batched detect ran in this 1.5 s: the reader finds nothing
    assert H.metric_reader("detect_ms_per_frame")(run) is None
    assert rec["expect"]["device_idle_pct.query"] == pytest.approx(idle)
    assert rec["expect"]["decode_roofline_pct"] == pytest.approx(roof)
