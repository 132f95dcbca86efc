"""Reductions over the device traces of several chips, on the slice
recorded on a TPU v5e (``data/trace_sample.json``) and on hand-made
traces, and the checks that keep a sharded configuration to what its
shards run."""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import tracereduce as tr  # noqa: E402
from benchmarks.chip.recorder import Recorder  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_sample.json")
READERS = ("device_idle_pct.query", "decode_roofline_pct")


def recorded():
    with open(DATA) as f:
        return json.load(f)


def run_of(rec, trace=None, traces=()):
    return H.Run("golden720-scan", H.load_config("vstore720-1toN"), {},
                 rec["device_kind"], spans=rec["spans"], trace=trace,
                 traces=list(traces))


def parent_busy_s(trace):
    """``busy_s`` as the benchmark computed it before it took lists."""
    lo, hi = tr.window_ns(trace)
    tot = sum(tr.covered([(s, s + d) for _n, s, d in chip["modules"]],
                         lo, hi) for chip in trace["chips"])
    return tot / len(trace["chips"]) / 1e9


def test_one_trace_in_a_list_reads_as_the_trace():
    rec = recorded()
    t = rec["trace"]
    assert tr.busy_s([t]) == tr.busy_s(t) == parent_busy_s(t)
    assert tr.idle_pct([t]) == tr.idle_pct(t)
    assert tr.slice_s([t]) == (t["t1_ns"] - t["t0_ns"]) / 1e9
    assert tr.top_modules([t]) == tr.top_modules(t)
    assert tr.idle_gaps([t], rec["spans"]) == tr.idle_gaps(t, rec["spans"])
    one, listed = run_of(rec, trace=t), run_of(rec, traces=[t])
    for name in READERS:
        assert H.metric_reader(name)(one) == H.metric_reader(name)(listed)
    # the numbers the recorded slice read when it was recorded
    assert H.metric_reader(READERS[0])(one) == \
        rec["expect"]["device_idle_pct.query"]
    assert H.metric_reader(READERS[1])(one) == \
        rec["expect"]["decode_roofline_pct"]


def test_two_copies_read_the_same_shares():
    rec = recorded()
    t = rec["trace"]
    a, b = copy.deepcopy(t), copy.deepcopy(t)
    a["pid"], b["pid"] = 1, 2
    one, two = run_of(rec, trace=t), run_of(rec, traces=[a, b])
    for name in READERS:
        assert H.metric_reader(name)(two) == H.metric_reader(name)(one)
    assert tr.busy_s([a, b]) == tr.busy_s(t)
    assert tr.slice_s([a, b]) == tr.slice_s(t)
    # device time is summed over the chips
    for (n1, s1), (n2, s2) in zip(tr.top_modules(t), tr.top_modules([a, b])):
        assert n1 == n2 and s2 == pytest.approx(2 * s1)


def chip(mods, t0, t1, pid=None, anchor_t=0.0):
    t = {"chips": [{"plane": "/device:TPU:0", "modules": mods}],
         "anchor_ns": 0.0, "anchor_t": anchor_t, "t0_ns": t0, "t1_ns": t1}
    if pid is not None:
        t["pid"] = pid
    return t


def span(name, pid, t0, d):
    return {"n": name, "s": 1, "p": 0, "t": 1, "t0": t0, "d": d, "pid": pid,
            "a": {}}


def test_idle_is_all_busy_time_over_all_windows():
    # chip 1 busy 100 of 1000 ns, chip 2 busy 600 of 2000 ns
    a = chip([["jit__resize(1)", 0.0, 100.0]], 0.0, 1000.0, pid=1)
    b = chip([["jit__resize(1)", 500.0, 600.0]], 0.0, 2000.0, pid=2)
    assert tr.idle_pct([a, b]) == pytest.approx(100.0 * (1 - 700 / 3000))
    assert tr.busy_s([a, b]) == pytest.approx(350e-9)
    assert tr.slice_s([a, b]) == pytest.approx(1500e-9)
    assert tr.top_modules([a, b]) == [["jit__resize", pytest.approx(7e-7)]]
    assert tr.idle_pct([]) is None and tr.busy_s([]) == 0.0


def test_each_chips_gaps_are_named_by_its_own_shard():
    a = chip([["jit__resize(1)", 0.0, 100.0]], 0.0, 1000.0, pid=1)
    b = chip([["jit__resize(1)", 0.0, 400.0]], 0.0, 1000.0, pid=2,
             anchor_t=5.0)
    spans = [span("convert", 1, 0.0, 1e-6),      # shard 0, on chip a's clock
             span("codec.entropy", 2, 5.0, 1e-6),  # shard 1
             span("query", 0, 0.0, 1e-5)]          # the router
    gaps = tr.idle_gaps([a, b], spans)
    assert gaps == [["convert", pytest.approx(900e-9)],
                    ["codec.entropy", pytest.approx(600e-9)]]


def cfg_1toN():
    return H.load_config("vstore720-1toN")


def server_of(cfg):
    return H.program_objects(cfg)[2]


def test_shard_opts_take_what_the_shard_stack_runs():
    server = server_of(cfg_1toN())
    opts = H.shard_server_opts(server)
    assert set(opts) == set(H.SHARD_SERVER_KEYS)
    assert all(opts[k] == server[k] for k in opts)


@pytest.mark.parametrize("change", [
    lambda s: s.update(colapse=True),         # no such setting
    lambda s: s.update(collapse=False),       # the shard stack drops it
    lambda s: s.update(batch_shapes=(8, 16)),  # the shard stack drops it
    lambda s: s.pop("workers"),               # the stack's default would run
], ids=["unknown", "collapse", "batch_shapes", "unstated"])
def test_a_server_setting_the_shards_would_not_run_raises(change):
    server = dict(server_of(cfg_1toN()))
    change(server)
    with pytest.raises(ValueError):
        H.shard_server_opts(server)


def test_a_deployment_needs_a_chip_a_shard_and_a_query_mix():
    cfg = dict(cfg_1toN(), deployment={"shards": 4})
    scan, ingest = H.T.load("scan"), H.T.load("ingest")
    cell = {"name": "c", "chips": 4}
    assert H.deployment_shards(cfg, cell, scan) == 4
    assert H.deployment_shards(cfg_1toN(), {"name": "c", "chips": 1},
                               scan) is None
    with pytest.raises(ValueError):
        H.deployment_shards(cfg, {"name": "c", "chips": 1}, scan)
    with pytest.raises(ValueError):
        H.deployment_shards(cfg, cell, ingest)
    for bad in ({"shards": "4"}, {"shards": 4, "replicas": 2}, {}):
        with pytest.raises(ValueError):
            H.deployment_shards(dict(cfg, deployment=bad), cell, scan)


def device(coords, platform="tpu", count=1, chip=None):
    return {"platform": platform, "kind": "TPU v5 lite", "count": count,
            "coords": [coords], "chip": chip}


def test_shards_must_hold_distinct_chips():
    good = [device([i % 2, i // 2, 0]) for i in range(4)]
    assert H.require_shard_chips(good, 4) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    # each shard a one-chip slice of its own, as on a v5e host: every
    # chip reads coords (0, 0, 0), the chip it was bound to differs
    bound = [device([0, 0, 0], chip=str(i)) for i in range(4)]
    assert H.require_shard_chips(bound, 4)["count"] == 4
    for bad in ([device([0, 0, 0])] * 4,
                [device([0, 0, 0], chip="0")] * 4,
                good[:3] + [device([1, 1, 0], platform="cpu")],
                good[:3] + [device([1, 1, 0], count=2)],
                good[:3]):
        with pytest.raises(RuntimeError):
            H.require_shard_chips(bad, 4)


def test_recorders_merge_across_shards():
    a, b = Recorder({}, set(), 4), Recorder({}, set(), 4)
    a.decodes[("cam0", 0, "sf")] = [("w", "f")]
    a.where[b"x"] = ("cam0", 0, "cf")
    a.read[("cam0", 0, "cf")] = {b"x"}
    a.blocks.append(("cf", b"x", None, set()))
    a.unread = 1
    b.where[b"x"] = ("cam4", 0, "cf")
    b.read[("cam4", 0, "cf")] = {b"x"}
    b.unread = 2
    m = Recorder({}, set(), 4)
    m.merge(a.state())
    m.merge(b.state())
    assert m.unread == 3 and len(m.blocks) == 1
    assert m.where[b"x"] == ("cam0", 0, "cf")
    assert set(m.read) == {("cam0", 0, "cf"), ("cam4", 0, "cf")}
    assert list(m.decodes) == [("cam0", 0, "sf")]
