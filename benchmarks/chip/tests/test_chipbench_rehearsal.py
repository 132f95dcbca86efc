"""Every cell of the chip benchmark, rehearsed on the CPU at the demo
camera grid: build, warm-up, a two-second window, the check and the
metrics, each found by name from ``BENCHMARK.json``.  Then the same runs
with the timed path broken underneath, which the check must refuse.

The test steers the harness (a small grid, the CPU accepted as the
device); the command itself refuses a CPU, which the last tests show."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import traffic as T  # noqa: E402

SMALL = {"height": 96, "width": 160, "fps": 8, "segment_seconds": 4,
         "nominal": 720}
BENCH = H.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture
def steer(monkeypatch):
    import jax
    orig = H.load_config

    def small(name):
        cfg = orig(name)
        cfg["spec"] = dict(SMALL)
        return cfg
    monkeypatch.setattr(H, "load_config", small)
    monkeypatch.setattr(H, "require_chips", lambda n: {
        "platform": "cpu", "kind": "cpu", "count": n,
        "devices": jax.devices()[:n]})
    monkeypatch.setattr(H, "require_shard_chips", lambda devices, n: {
        "platform": "cpu", "kind": "cpu", "count": n})


def run(cell, seed=7, trace=False, seconds=2.0):
    return H.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                      log=lambda m: None)


def test_every_named_file_is_found():
    for c in BENCH["configs"]:
        cfg = H.load_config(c["name"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert key in cfg and key in cfg["reduced"]
        server = H.program_objects(cfg)[2]
        if "deployment" in cfg:
            H.shard_server_opts(server)
    for w in BENCH["workloads"]:
        assert T.load(w["traffic"])["kind"] in ("query", "ingest")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(H.metric_reader(m["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(steer, cell):
    line, checks = run(cell)
    assert line["correct"], checks
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in H.cell_metrics(BENCH, cell, "end_to_end")}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_traced(steer, cell):
    line, checks = run(cell, trace=True)
    assert line["correct"], checks
    # spans are read on any backend; the device trace only on a TPU
    spans_only = {m["name"] for m in H.cell_metrics(BENCH, cell, "per_layer")
                  if m["source"] == "program_span"}
    assert spans_only <= set(line["metrics"])


def _break(monkeypatch, obj, name, wrap):
    monkeypatch.setattr(obj, name, wrap(getattr(obj, name)))


def test_altered_decode_is_refused(steer, monkeypatch):
    from repro.videostore import VideoStore

    def wrap(orig):
        def decode_for(self, *a, **k):
            frames, cost = orig(self, *a, **k)
            frames = frames.copy()
            frames[:, :8, :8] ^= 0x40   # an answer altered where produced
            return frames, cost
        return decode_for
    _break(monkeypatch, VideoStore, "decode_for", wrap)
    line, checks = run("golden720-scan")
    assert not line["correct"]
    assert checks["decode_max_abs"]["value"] > checks["decode_max_abs"]["limit"]


def test_half_the_batch_left_out_is_refused(steer, monkeypatch):
    import jax.numpy as jnp

    from repro.analytics import operators as ops

    def wrap(orig):
        def scores(x):
            # half of the batch left out, its scores the mean of the rest
            half = orig(x[: len(x) // 2 + 1])
            rest = jnp.full((len(x) - 1 - len(half),), half.mean())
            return jnp.concatenate([half, rest])
        return scores
    _break(monkeypatch, ops, "_diff_scores", wrap)
    line, checks = run("2sf720-scan")
    assert not line["correct"]
    assert checks["score_err"]["value"] > checks["score_err"]["limit"]


def test_altered_conversion_is_refused(steer, monkeypatch):
    from repro.codec import transform as Tr

    def wrap(orig):
        def spatial_convert(*a, **k):
            return orig(*a, **k) // 2 * 2   # drops the lowest bit
        return spatial_convert
    _break(monkeypatch, Tr, "spatial_convert", wrap)
    line, checks = run("2sf720-scan")
    assert not line["correct"]
    assert checks["convert_off_share"]["value"] > \
        checks["convert_off_share"]["limit"]


@pytest.mark.parametrize("op", ["diff", "motion"])
def test_stage0_outputs_out_of_sight_are_refused(steer, monkeypatch, op):
    """The operator computes its per-frame outputs under a name the
    recorder does not wrap, as a fused program would: nothing of stage 0
    is compared, and that must not read as correct."""
    import types

    from repro.analytics import operators as ops
    cls = type(ops.OPERATORS[op])
    orig = cls.detect
    unseen = types.FunctionType(orig.__code__, dict(orig.__globals__),
                                orig.__name__, orig.__defaults__,
                                orig.__closure__)
    monkeypatch.setattr(cls, "detect", unseen)
    line, checks = run("2sf720-scan")
    assert not line["correct"]
    assert checks["uncompared"]["value"] > checks["uncompared"]["limit"]


def test_stage0_called_past_the_recorder_is_refused(steer, monkeypatch):
    """The query plan calls another Diff object, so no stage-0 call of
    query A reaches the one the recorder wraps."""
    import copy

    from repro.analytics import operators as ops
    from repro.analytics import query
    monkeypatch.setattr(query, "OPERATORS", {
        **ops.OPERATORS, "diff": copy.copy(ops.OPERATORS["diff"])})
    line, checks = run("golden720-scan")
    assert not line["correct"]
    assert checks["uncompared"]["value"] > checks["uncompared"]["limit"]


def test_a_segment_holding_another_segments_bytes_is_refused(steer,
                                                            monkeypatch):
    """cam0's segment 1 holds segment 0's bytes: every read of it converts
    other frames than its pooled segment's."""
    from repro.videostore import VideoStore
    last = {}

    def wrap(orig):
        def put_segment(self, stream, seg, sf_id, blob, *a, **k):
            prev, last[sf_id] = last.get(sf_id, blob), blob
            if (stream, seg) == ("cam0", 1):
                blob = prev
            return orig(self, stream, seg, sf_id, blob, *a, **k)
        return put_segment
    _break(monkeypatch, VideoStore, "put_segment", wrap)
    line, checks = run("golden720-scan")
    assert not line["correct"]
    assert checks["item_mismatch"]["value"] > checks["item_mismatch"]["limit"]


def test_unwritten_derived_format_is_refused(steer, monkeypatch):
    from repro.videostore import VideoStore

    def wrap(orig):
        def put_segment(self, stream, seg, sf_id, blob, *a, **k):
            if sf_id != "sf_g" and stream != "warm":
                return None   # a step that leaves the store unchanged
            return orig(self, stream, seg, sf_id, blob, *a, **k)
        return put_segment
    _break(monkeypatch, VideoStore, "put_segment", wrap)
    line, checks = run("2sf720-ingest")
    assert not line["correct"]
    assert checks["missing"]["value"] > 0


def test_another_segments_bytes_are_refused(steer, monkeypatch):
    from repro.videostore import VideoStore
    last = {}

    def wrap(orig):
        def put_segment(self, stream, seg, sf_id, blob, *a, **k):
            # the golden bytes written are the previous segment's: an
            # answer altered where it is produced
            prev = last.get(sf_id, blob)
            last[sf_id] = blob
            if sf_id == "sf_g" and stream != "warm":
                blob = prev
            return orig(self, stream, seg, sf_id, blob, *a, **k)
        return put_segment
    _break(monkeypatch, VideoStore, "put_segment", wrap)
    line, checks = run("2sf720-ingest")
    assert not line["correct"]
    assert checks["symbol_off_share"]["value"] > \
        checks["symbol_off_share"]["limit"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    out = _cli(ROOT)
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{")
    assert "no accelerator" in out.stderr


def test_command_needs_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(str(tmp_path), {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert json.loads(open(tmp_path / "BENCHMARK.json").read())


def test_benchmark_json_keeps_its_shape():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        # one chip, or one chip a shard of the configuration's deployment
        shards = H.deployment_shards(H.load_config(w["config"]), w,
                                     T.load(w["traffic"]))
        assert w["chips"] == (shards or 1)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    moves = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in moves and "\n" not in m["layer"]
    for x in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
              + BENCH["per_layer"]):
        assert name.match(x["name"]) and x["name"] not in names
        names.add(x["name"])
    for w in BENCH["workloads"]:
        reports = [m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                   if w["name"] in m.get("workloads", CELLS)]
        assert any(m["name"] == "setup_s" for m in reports)
        assert len([m for m in reports if m in BENCH["end_to_end"]]) >= 2
        assert any(m in BENCH["per_layer"] for m in reports)
