"""``cluster720-scan`` rehearsed on the CPU: its configuration (16
cameras on 4 shards behind ``ShardRouter``) at the demo grid, from
``data/vstore-4shard-small.json``, through the harness's own build,
warm-up, window, check and metrics, untraced and traced.  Then the same
run with another segment's bytes under a camera's key inside a shard,
which the check must refuse.

Each run is a process of its own (this file run as a script), as in
``test_chipbench_sharded.py``: the harness process must open no JAX
backend, and a fault planted before the shards start reaches them
through ``REPRO_CLUSTER_START_METHOD=fork``.  The run steers the harness
only where a CPU needs it: the configuration's grid, and the CPU
accepted as the shards' device."""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "cluster720-scan"
SMALL = "vstore-4shard-small"
WIRE = ("rpc_overhead_ms_per_query", "wire_bytes_per_query")
SPANS = ("convert_ms_per_seg", "detect_ms_per_frame", "store_get_ms_per_seg",
         "entropy_ms_per_seg")


# -- the run, in a process of its own -----------------------------------------------

def steer(H, seen: dict):
    """The cell's configuration at the demo grid; CPU shards taken; the
    window's records and the wire tallies of the threads that drive the
    router kept in ``seen``."""
    from repro.obs import trace as obs
    with open(os.path.join(DATA, f"{SMALL}.json")) as f:
        over = json.load(f)
    load_config, closed_loop = H.load_config, H.closed_loop
    query = H.RouterClient._query

    def config(name):
        cfg = load_config(name)
        if name == over["base"]:
            cfg["spec"] = over["spec"]
        return cfg

    def cpu_chips(devices, n):
        if len(devices) != n or any(d["count"] != 1 for d in devices):
            raise RuntimeError(f"not one device a shard: {devices}")
        return {"platform": "cpu", "kind": "cpu", "count": n}

    def loop(*a, **k):
        seen["records"] = closed_loop(*a, **k)
        return seen["records"]

    def routed(self, *a, **k):
        try:
            return query(self, *a, **k)
        finally:
            tally = getattr(obs.TRACER._tls, "tally", None) or {}
            seen["wire"].update(n for n in tally if n.startswith("wire_"))

    H.load_config = config
    H.require_shard_chips = cpu_chips
    H.closed_loop = loop
    H.RouterClient._query = routed


def plant(fault):
    """Another segment's bytes under a camera's key: cam0's segment 1
    holds the blob written before it, segment 0's.  The shards, forked
    from this process, inherit the break."""
    from repro.videostore import VideoStore
    if fault == "none":
        return
    if fault != "bytes":
        raise ValueError(fault)
    orig, last = VideoStore.put_segment, {}

    def put_segment(self, stream, seg, sf_id, blob, *a, **k):
        prev, last[sf_id] = last.get(sf_id, blob), blob
        if (stream, seg) == ("cam0", 1):
            blob = prev
        return orig(self, stream, seg, sf_id, blob, *a, **k)
    VideoStore.put_segment = put_segment


def main(fault: str, seed: int, trace: bool, seconds: float) -> None:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from jax._src import xla_bridge

    from benchmarks.chip import harness as H
    from repro.cluster.router import stable_shard
    seen = {"records": ([], 0, 0), "wire": set()}
    steer(H, seen)
    plant(fault)
    line, checks = H.run_cell(CELL, seed, seconds, trace,
                              time.perf_counter(),
                              log=lambda m: print(m, file=sys.stderr))
    cell = {w["name"]: w for w in H.load_benchmark()["workloads"]}[CELL]
    cfg = H.load_config(cell["config"])
    shards = cfg["deployment"]["shards"]
    answered = sorted({stable_shard(r["stream"], shards)
                       for r in seen["records"][0] if r.get("ok")})
    print(json.dumps({"line": line, "checks": checks,
                      "cameras": len(H.cameras(cfg)),
                      "shards_answered": answered,
                      "wire_tallies": sorted(seen["wire"]),
                      "backend": xla_bridge.backends_are_initialized()}))


# -- the tests ------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """One compile cache for the runs of this file: only the first
    compiles."""
    return str(tmp_path_factory.mktemp("jax_cache"))


def drive(cache, fault="none", seed=21, trace=False, seconds=5.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if fault != "none":
        env["REPRO_CLUSTER_START_METHOD"] = "fork"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), fault, str(seed),
         str(int(trace)), str(seconds)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [False, True], ids=["scan", "scan-traced"])
def test_cluster_rehearsal(cache, trace):
    got = drive(cache, trace=trace)
    line, checks = got["line"], got["checks"]
    # the check needs each sampled segment read under both stage-0
    # formats: two cycles of the 32 ranges
    assert line["attempted"] >= 64
    assert line["correct"], checks
    assert not got["backend"]   # the harness process never opened one
    assert got["cameras"] == 16
    assert got["shards_answered"] == [0, 1, 2, 3]
    assert line["failed"] == 0
    dev = line["device"]
    assert dev["count"] == 4 and len(dev["memory_peak_bytes_per_chip"]) == 4
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"query_xrt", "setup_s"}
        assert all(v["value"] > 0 for v in metrics.values())
        # the wire's count returned before touching a tally
        assert got["wire_tallies"] == []
    else:
        assert set(SPANS + WIRE) <= set(metrics)
        assert all(metrics[m]["value"] > 0 for m in SPANS + WIRE)
        assert got["wire_tallies"] == ["wire_rx_bytes", "wire_tx_bytes"]


def test_cluster_break_is_refused(cache):
    got = drive(cache, fault="bytes", seed=4)
    assert not got["line"]["correct"]
    number = got["checks"]["item_mismatch"]
    assert number["value"] > number["limit"]


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), bool(int(sys.argv[3])),
         float(sys.argv[4]))
