"""The one generator of traffic.  A mix is a data file,
``traffic/<name>.json``; this module turns it and a seed into requests.

Query mixes (``"kind": "query"``):

* ``"loop": "closed"`` -- ``clients`` callers, each sending its next
  request when the last returns.  Requests take the archive's ranges
  (``segments_per_request`` consecutive segments of one camera) in a
  cyclic order drawn from the seed, and the ``mix`` of (query, accuracy)
  in turn.
* ``"loop": "open"`` -- ``rate_per_s`` arrivals a second, for the whole
  window: ``round(rate * seconds)`` arrival times spread uniformly (a
  Poisson process given its count), each over camera ``camera``'s
  newest ``segments_per_request`` segments.  Every seed gets the same
  number of requests of each kind, in another order.

Ingest mixes (``"kind": "ingest"``): ``cameras`` cameras each push the
seed's pooled segments back to back, a closed loop per camera.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), salt])


def all_ranges(traffic: dict, cameras: list[str], segments: int) -> list:
    """Every ``(camera, [segments])`` range the mix reads."""
    k = traffic["segments_per_request"]
    if traffic["loop"] == "closed":
        return [(c, list(range(s, s + k))) for c in cameras
                for s in range(0, segments - k + 1, k)]
    return [(cameras[traffic["camera"]], list(range(segments - k, segments)))]


def closed_request(traffic: dict, ranges: list, order: np.ndarray,
                   i: int) -> tuple:
    """Request ``i`` of a closed loop: ``(query, camera, segs, accuracy)``.
    The range cycles through ``order``; the (query, accuracy) kind
    advances by one a request and shifts by one a cycle, so every range
    meets every kind."""
    r = len(order)
    cam, segs = ranges[order[i % r]]
    q, acc = traffic["mix"][(i + i // r) % len(traffic["mix"])]
    return q, cam, list(segs), float(acc)


def closed_order(ranges: list, seed: int) -> np.ndarray:
    """The seed's cyclic order of the ranges."""
    return _rng(seed, 1).permutation(len(ranges))


def open_arrivals(traffic: dict, ranges: list, seed: int,
                  seconds: float) -> list[tuple]:
    """``[(due_s, (query, camera, segs, accuracy)), ...]`` sorted by due
    time, offsets from the window's start."""
    n = int(round(traffic["rate_per_s"] * seconds))
    rng = _rng(seed, 2)
    times = np.sort(rng.uniform(0.0, seconds, n))
    mix = traffic["mix"]
    kinds = [mix[i % len(mix)] for i in rng.permutation(n)]
    (cam, segs), = ranges
    return [(float(t), (q, cam, list(segs), float(acc)))
            for t, (q, acc) in zip(times, kinds)]


def warm_requests(traffic: dict, ranges: list, content) -> list[tuple]:
    """One request of every kind over every range whose content differs:
    the shapes a window can meet, compiled before it.  ``content(cam,
    seg)`` names the pooled segment a camera's segment holds."""
    seen, out = set(), []
    for cam, segs in ranges:
        sig = tuple(content(cam, s) for s in segs)
        if sig in seen:
            continue
        seen.add(sig)
        out += [(q, cam, list(segs), float(acc)) for q, acc in traffic["mix"]]
    return out
