"""Cluster wire protocol: length-prefixed msgpack frames + explicit wire
forms for every object that crosses the process boundary.

The sharded store is the first place VStore objects leave their process, so
each layer's payload gets a deliberate serialized form here instead of
pickle: pickle would silently couple the worker to the router's class
layout (and break under ``spawn`` for locally-defined config stand-ins like
the launchers' ``_Log``).  Frames are ``4-byte big-endian length +
msgpack(payload)``; payloads are plain scalars/lists/dicts plus one tagged
extension for numpy arrays (``{_ND_TAG: [shape, dtype, bytes]}``, used to
ship ingest frames without a base64 detour).

Wire forms provided here:

* ``pack``/``unpack`` + ``send_msg``/``recv_msg`` — framing;
* ``config_to_wire``/``config_from_wire`` — a ``DerivedConfig``'s consumer
  plans and SF nodes (knob values only; the receiving worker rebuilds the
  dataclasses and lookup tables);
* ``spec_to_wire``/``spec_from_wire`` — the ``IngestSpec`` grid;
* ``erosion_plan_to_wire``/``..from_wire`` — an ``ErosionPlan`` so workers
  can run cluster-coordinated erosion passes;
* ``QueryResult.to_wire``/``from_wire`` live with the dataclass itself
  (``repro.analytics.query``).
"""

from __future__ import annotations

import dataclasses
import struct

import msgpack
import numpy as np

from ..core.coalesce import SFNode
# analysis: allow[wire-field] DerivedConfig.erosion is deliberately not
# in the config frame: the erosion plan ships separately (opts
# ["erosion_plan"], erosion_plan_to_wire) so workers can rebuild their
# ErosionExecutor without re-deriving the whole config
from ..core.configure import DerivedConfig
from ..core.consumption import Consumer, ConsumerPlan
from ..core.erosion import ErosionPlan
from ..core.knobs import CodingOption, FidelityOption, IngestSpec
from ..obs import trace as obs

_LEN = struct.Struct(">I")
_ND_TAG = "__nd__"
MAX_FRAME = 256 << 20  # corrupt-length guard, not a real payload limit


class WireError(ConnectionError):
    """Framing-level failure (peer closed mid-frame, oversized frame)."""


# -- numpy passthrough -------------------------------------------------------

def _default(obj):
    if isinstance(obj, np.ndarray):
        arr = np.ascontiguousarray(obj)
        return {_ND_TAG: [list(arr.shape), arr.dtype.str, arr.tobytes()]}
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    raise TypeError(f"not wire-serializable: {type(obj).__name__}")


def _object_hook(d):
    nd = d.get(_ND_TAG)
    if nd is not None:
        shape, dtype, raw = nd
        return np.frombuffer(raw, dtype=np.dtype(dtype)).reshape(shape).copy()
    return d


def pack(obj) -> bytes:
    return msgpack.packb(obj, default=_default, use_bin_type=True)


def unpack(blob: bytes):
    return msgpack.unpackb(blob, object_hook=_object_hook, raw=False,
                           strict_map_key=False)


# -- framing over a stream socket -------------------------------------------

def send_msg(sock, obj) -> None:
    """Send one frame; while tracing, the thread's ``wire_tx_bytes`` tally
    counts it, length prefix included."""
    blob = pack(obj)
    sock.sendall(_LEN.pack(len(blob)) + blob)
    obs.count("wire_tx_bytes", _LEN.size + len(blob))


def _recv_exact(sock, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise WireError("peer closed mid-frame")
        buf.extend(chunk)
    return bytes(buf)


def recv_msg(sock):
    """Receive one frame; while tracing, the thread's ``wire_rx_bytes``
    tally counts it, length prefix included."""
    n = _LEN.unpack(_recv_exact(sock, _LEN.size))[0]
    if n > MAX_FRAME:
        raise WireError(f"frame of {n} bytes exceeds MAX_FRAME")
    blob = _recv_exact(sock, n)
    obs.count("wire_rx_bytes", _LEN.size + n)
    return unpack(blob)


# -- IngestSpec --------------------------------------------------------------

def spec_to_wire(spec: IngestSpec) -> dict:
    return dataclasses.asdict(spec)


def spec_from_wire(d: dict) -> IngestSpec:
    return IngestSpec(**d)


# -- DerivedConfig -----------------------------------------------------------

def _fidelity_to_wire(f: FidelityOption) -> list:
    return [f.quality, f.crop, f.resolution, f.sampling]


def _fidelity_from_wire(v) -> FidelityOption:
    q, crop, res, samp = v
    return FidelityOption(q, crop, res, samp)


def _coding_to_wire(c: CodingOption) -> list:
    return [c.speed, c.keyframe, c.bypass]


def _coding_from_wire(v) -> CodingOption:
    speed, keyframe, bypass = v
    return CodingOption(speed, keyframe, bypass)


@dataclasses.dataclass
class _WireCoalesceLog:
    """Minimal coalesce-log stand-in for a config rebuilt from the wire
    (the coalescing transcript itself stays on the frontend)."""
    nodes: list
    ingest_cost: float = 0.0
    storage_cost: float = 0.0
    rounds: list = dataclasses.field(default_factory=list)
    budget_met: bool = True


def config_to_wire(config: DerivedConfig) -> dict:
    """Serialize the parts of a ``DerivedConfig`` query execution reads:
    consumer plans and SF nodes.  Plans are indexed so node membership
    round-trips as shared references."""
    plan_idx = {id(p): i for i, p in enumerate(config.plans)}
    return {
        "plans": [{
            "op": p.consumer.op, "target": p.consumer.target,
            "cf": _fidelity_to_wire(p.cf), "accuracy": p.accuracy,
            "speed": p.speed,
        } for p in config.plans],
        "nodes": [{
            "fidelity": _fidelity_to_wire(n.fidelity),
            "coding": _coding_to_wire(n.coding),
            "plans": [plan_idx[id(p)] for p in n.plans],
            "golden": n.golden,
        } for n in config.nodes],
        "dct_backend": config.dct_backend,
        "index_ops": (list(config.index_ops)
                      if config.index_ops is not None else None),
    }


def config_from_wire(d: dict) -> DerivedConfig:
    plans = [ConsumerPlan(Consumer(p["op"], p["target"]),
                          _fidelity_from_wire(p["cf"]),
                          p["accuracy"], p["speed"]) for p in d["plans"]]
    nodes = [SFNode(_fidelity_from_wire(n["fidelity"]),
                    _coding_from_wire(n["coding"]),
                    [plans[i] for i in n["plans"]],
                    golden=n["golden"]) for n in d["nodes"]]
    index_ops = d.get("index_ops")
    return DerivedConfig(plans=plans, nodes=nodes,
                         coalesce_log=_WireCoalesceLog(nodes=nodes),
                         dct_backend=d.get("dct_backend"),
                         index_ops=(tuple(index_ops)
                                    if index_ops is not None else None))


# -- ErosionPlan -------------------------------------------------------------

def erosion_plan_to_wire(plan: ErosionPlan) -> dict:
    d = dataclasses.asdict(plan)
    # msgpack maps stringify nothing here (strict_map_key=False lets int
    # keys through), but normalize to lists of [idx, frac] pairs anyway so
    # the wire form is self-describing
    d["fractions"] = [sorted(f.items()) for f in plan.fractions]
    return d


def erosion_plan_from_wire(d: dict) -> ErosionPlan:
    d = dict(d)
    d["fractions"] = [{int(i): float(v) for i, v in pairs}
                      for pairs in d["fractions"]]
    return ErosionPlan(**d)
