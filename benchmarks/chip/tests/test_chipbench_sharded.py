"""The sharded path rehearsed on the CPU: query cells of
``data/vstore-2shard-small.json`` (vstore720-1toN's formats on 2 shards
behind ``ShardRouter``, at the demo grid) under the ``scan`` mix and the
open-loop ``hot`` mix, through the harness's own build, warm-up, window,
check and metrics.  Then the same runs with the timed path broken inside
a shard, which the check must refuse.

Each run is a process of its own (this file run as a script), so that it
can show that the harness process opens no JAX backend, and so that the
faults, planted in it before its shards start, reach the shards through
``REPRO_CLUSTER_START_METHOD=fork``.  The run steers the harness as
``test_chipbench_rehearsal.py`` does: the test configuration and a cell of
it for each mix added by name, and the CPU accepted as the shards'
device."""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIG = "vstore-2shard-small"
# a cell of the test configuration for each mix; each reports what the
# one-chip cell of vstore720-1toN under that mix reports
LIKE = {"scan": "golden720-scan", "hot": "golden720-hot"}
SPANS = ("convert_ms_per_seg", "detect_ms_per_frame", "store_get_ms_per_seg",
         "entropy_ms_per_seg")


# -- the run, in a process of its own -----------------------------------------------

def cell(traffic):
    return f"sharded-{traffic}"


def steer(H):
    """The test configuration and cells found by name; CPU shards taken."""
    bench, load_config = H.load_benchmark(), H.load_config
    for traffic, like in LIKE.items():
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell(traffic))
        bench["workloads"].append({"name": cell(traffic), "config": CONFIG,
                                   "traffic": traffic, "chips": 2,
                                   "why": "rehearsal"})

    def config(name):
        if name != CONFIG:
            return load_config(name)
        with open(os.path.join(DATA, f"{name}.json")) as f:
            over = json.load(f)
        cfg = load_config(over.pop("base"))
        cfg.update(over)
        return cfg

    def cpu_chips(devices, n):
        if len(devices) != n or any(d["count"] != 1 for d in devices):
            raise RuntimeError(f"not one device a shard: {devices}")
        return {"platform": "cpu", "kind": "cpu", "count": n}

    H.load_benchmark = lambda: bench
    H.load_config = config
    H.require_shard_chips = cpu_chips


def plant(fault):
    """Break the timed path; the shards, forked from this process, inherit
    the break."""
    from repro.videostore import VideoStore
    if fault == "decode":
        orig = VideoStore.decode_for

        def decode_for(self, *a, **k):
            frames, cost = orig(self, *a, **k)
            frames = frames.copy()
            frames[:, :8, :8] ^= 0x40   # an answer altered where produced
            return frames, cost
        VideoStore.decode_for = decode_for
    elif fault == "unseen":
        # Diff computes its per-frame outputs under a name the recorder
        # does not wrap, as a fused program would
        import types

        from repro.analytics import operators as ops
        cls = type(ops.OPERATORS["diff"])
        f = cls.detect
        cls.detect = types.FunctionType(f.__code__, dict(f.__globals__),
                                        f.__name__, f.__defaults__,
                                        f.__closure__)
    elif fault == "bytes":
        orig, last = VideoStore.put_segment, {}

        def put_segment(self, stream, seg, sf_id, blob, *a, **k):
            # cam0's segment 1 holds the bytes written before it, segment
            # 0's: another segment's bytes under a camera's key
            prev, last[sf_id] = last.get(sf_id, blob), blob
            if (stream, seg) == ("cam0", 1):
                blob = prev
            return orig(self, stream, seg, sf_id, blob, *a, **k)
        VideoStore.put_segment = put_segment
    elif fault != "none":
        raise ValueError(fault)


def main(traffic: str, fault: str, seed: int, trace: bool,
         seconds: float) -> None:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from jax._src import xla_bridge

    from benchmarks.chip import harness as H
    steer(H)
    plant(fault)
    line, checks = H.run_cell(cell(traffic), seed, seconds, trace,
                              time.perf_counter(),
                              log=lambda m: print(m, file=sys.stderr))
    print(json.dumps({"line": line, "checks": checks,
                      "backend": xla_bridge.backends_are_initialized()}))


# -- the tests ------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """One compile cache for the runs of this file: only the first
    compiles."""
    return str(tmp_path_factory.mktemp("jax_cache"))


def drive(cache, traffic="scan", fault="none", seed=11, trace=False,
          seconds=1.5):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if fault != "none":
        env["REPRO_CLUSTER_START_METHOD"] = "fork"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), traffic, fault,
         str(seed), str(int(trace)), str(seconds)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("traffic, trace, seconds", [
    ("scan", False, 1.5), ("scan", True, 1.5),
    ("hot", False, 0.5),    # the open loop: 400 arrivals through the router
], ids=["scan", "scan-traced", "hot"])
def test_sharded_rehearsal(cache, traffic, trace, seconds):
    got = drive(cache, traffic=traffic, trace=trace, seconds=seconds)
    line, checks = got["line"], got["checks"]
    assert line["correct"], checks
    assert not got["backend"]   # the harness process never opened one
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["count"] == 2 and len(dev["memory_peak_bytes_per_chip"]) == 2
    assert dev["memory_peak_bytes"] == max(dev["memory_peak_bytes_per_chip"])
    metrics = line["metrics"]
    if not trace:
        want = {"scan": "query_xrt", "hot": "query_p95_s"}[traffic]
        assert {want, "setup_s"} <= set(metrics)
        assert all(v["value"] > 0 for v in metrics.values())
    else:
        assert set(SPANS) <= set(metrics)
        assert all(metrics[m]["value"] > 0 for m in SPANS)


@pytest.mark.parametrize("fault, number, seed", [
    ("decode", "decode_max_abs", 12),
    ("unseen", "uncompared", 12),
    # on seed 4 neither a compared operator call nor the sampled segments
    # see cam0's segment 1: only the check of every segment read does
    ("bytes", "item_mismatch", 4),
])
def test_sharded_break_is_refused(cache, fault, number, seed):
    got = drive(cache, fault=fault, seed=seed, seconds=1.0)
    assert not got["line"]["correct"]
    assert got["checks"][number]["value"] > got["checks"][number]["limit"]


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]), bool(int(sys.argv[4])),
         float(sys.argv[5]))
