"""The reader of ``entropy_staged_bytes_per_seg``: on hand-made
``codec.entropy`` spans, and silent on spans of programs whose entropy
spans carry no ``staged_bytes`` (the slices of ``data/trace_sample.json``
and ``data/host_sample.json``)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness as H  # noqa: E402

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = H.load_benchmark()
NAME = "entropy_staged_bytes_per_seg"


def span(name, sid, t0, **attrs):
    return {"n": name, "s": sid, "p": 0, "t": 1, "t0": t0, "d": 0.5,
            "a": attrs}


def run_of(workload, spans):
    cell = {w["name"]: w for w in BENCH["workloads"]}[workload]
    return H.Run(workload, H.load_config(cell["config"]), {}, "TPU v5 lite",
                 records=[{"due": 0.0}], spans=spans)


def test_staged_bytes_per_segment():
    """Zero where every entropy span staged nothing (a reading, not
    silence); otherwise the staged bytes over the segments decoded, one
    where a single-segment span carries no ``segments``."""
    read = H.metric_reader(NAME)
    direct = [span("codec.entropy", 1, 0.0, direct_chunks=1,
                   staged_bytes=0),
              span("codec.entropy", 2, 1.0, direct_chunks=3,
                   staged_bytes=0, segments=3)]
    assert read(run_of("golden720-scan", direct)) == 0.0
    staged = direct + [span("codec.entropy", 3, 2.0, staged_bytes=1200,
                            segments=2)]
    assert read(run_of("golden720-scan", staged)) == pytest.approx(1200 / 6)


def test_has_a_reader_and_cells():
    m = {x["name"]: x for x in BENCH["per_layer"]}[NAME]
    assert m["workloads"] and callable(H.metric_reader(NAME))
    for w in m["workloads"]:
        reports = {e["name"] for e in H.cell_metrics(BENCH, w, "end_to_end")}
        assert m["moves"] in reports


@pytest.mark.parametrize("sample,part", [("trace_sample.json", None),
                                         ("host_sample.json", "scan")])
def test_silent_on_a_program_without_the_counter(sample, part):
    """Both slices were recorded from programs whose ``codec.entropy``
    spans carry no ``staged_bytes``: the reader gives None, and raises
    nothing."""
    with open(os.path.join(HERE, sample)) as f:
        rec = json.load(f)
    spans = (rec[part] if part else rec)["spans"]
    assert any(sp["n"] == "codec.entropy" for sp in spans)
    for w in ("golden720-scan", "2sf720-ingest"):
        assert H.metric_reader(NAME)(run_of(w, spans)) is None
