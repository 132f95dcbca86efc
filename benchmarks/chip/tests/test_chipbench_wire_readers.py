"""The readers of the router and wire layer, ``rpc_overhead_ms_per_query``
and ``wire_bytes_per_query``: on hand-made spans of a sharded run (the
router's ``pid`` 0, shard i's ``i + 1``), and silent on the spans of
one-chip runs recorded on the chip (``data/trace_sample.json``,
``data/host_sample.json``)."""

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
NAMES = ("rpc_overhead_ms_per_query", "wire_bytes_per_query")


def span(name, sid, parent, t0, d, pid, **attrs):
    return {"n": name, "s": sid, "p": parent, "t": 1, "t0": t0, "d": d,
            "pid": pid, "tid": 1, "a": attrs}


def run_of(workload, spans):
    cell = {w["name"]: w for w in BENCH["workloads"]}[workload]
    return H.Run(workload, H.load_config(cell["config"]), {}, "TPU v5 lite",
                 records=[{"due": 0.0}], spans=spans)


def sharded_spans():
    """Two requests through the router, to shards 0 and 2.  Each shard's
    spans sit on a clock of its own (offsets 1000 s and -37 s off the
    router's), as spans harvested without re-basing would."""
    return [
        span("query", 1, 0, 0.0, 1.2, 0, query="A"),
        span("rpc:query", 2, 1, 0.01, 1.18, 0, shard=0,
             wire_tx_bytes=400, wire_rx_bytes=3600),
        span("query", 3, 2, 1000.02, 1.17, 1),
        span("retrieve", 4, 3, 1000.03, 0.5, 1),
        span("query", 5, 0, 2.0, 0.9, 0, query="B"),
        span("rpc:query", 6, 5, 2.0, 0.89, 0, shard=2,
             wire_tx_bytes=420, wire_rx_bytes=1580),
        span("query", 7, 6, -35.0, 0.86, 3),
        # another op's exchange: neither a query's overhead nor its bytes
        span("rpc:spans", 8, 0, 3.0, 0.2, 0, shard=1,
             wire_tx_bytes=30, wire_rx_bytes=90000),
    ]


def test_overhead_is_the_rpc_span_less_its_shard_child():
    got = H.metric_reader(NAMES[0])(run_of("cluster720-scan",
                                           sharded_spans()))
    assert got == pytest.approx(1e3 * ((1.18 - 1.17) + (0.89 - 0.86)) / 2)


def test_a_rpc_span_without_a_shard_child_is_left_out():
    spans = sharded_spans() + [span("rpc:query", 9, 1, 4.0, 5.0, 0)]
    got = H.metric_reader(NAMES[0])(run_of("cluster720-scan", spans))
    assert got == pytest.approx(20.0)


def test_bytes_are_summed_over_rpc_query_only():
    got = H.metric_reader(NAMES[1])(run_of("cluster720-scan",
                                           sharded_spans()))
    assert got == (400 + 3600 + 420 + 1580) / 2


def test_silent_where_the_wire_counts_nothing():
    """The parent program's rpc spans carry no wire bytes: None, not 0."""
    spans = [dict(sp, a={k: v for k, v in sp["a"].items()
                         if not k.startswith("wire_")})
             for sp in sharded_spans()]
    assert H.metric_reader(NAMES[1])(run_of("cluster720-scan", spans)) \
        is None


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("sample,part", [("trace_sample.json", None),
                                         ("host_sample.json", "scan")])
def test_silent_on_a_one_chip_run(name, sample, part):
    with open(os.path.join(HERE, sample)) as f:
        rec = json.load(f)
    spans = (rec[part] if part else rec)["spans"]
    assert any(sp["n"] == "query" for sp in spans)
    assert H.metric_reader(name)(run_of("golden720-scan", spans)) is None


@pytest.mark.parametrize("name", NAMES)
def test_entry_names_the_cluster_cell_only(name):
    m = {x["name"]: x for x in BENCH["per_layer"]}[name]
    assert m["workloads"] == ["cluster720-scan"]
    assert m["layer"] == "router and wire (cluster/router.py, cluster/wire.py)"
    reports = {e["name"] for e in H.cell_metrics(BENCH, "cluster720-scan",
                                                  "end_to_end")}
    assert m["moves"] in reports
