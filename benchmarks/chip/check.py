"""The comparison that decides ``correct``: what the timed path produced
against the float64 references of ``reference.py``.

Every number compared has its limit in ``limits.json``; a run is correct
when each number is at or under its limit.  The same functions judge the
lower-precision control (``control.py``), so that the control is held to
exactly what a run is held to.
"""

from __future__ import annotations

import json
import os

import numpy as np

from . import reference as R

_LIMITS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "limits.json")
# an item that differs counts as explained only where the reference's own
# score lies this close to the threshold (a share of the threshold): float
# rounding can put such a frame on either side
NEAR_THRESHOLD = 1e-3


def limits() -> dict:
    with open(_LIMITS) as f:
        return {k: v["limit"] for k, v in json.load(f).items()}


class Tally:
    """Numbers compared in one run, each the worst over what was seen."""

    def __init__(self):
        self.max_abs: dict[str, int] = {}
        self.off: dict[str, list[int]] = {}
        self.worst: dict[str, float] = {}
        self.count: dict[str, int] = {}

    def frames(self, name: str, got: np.ndarray, ref: np.ndarray) -> None:
        """Pixels (or symbols) against the reference: the largest
        absolute difference and how many differ at all."""
        self.counts(name, frame_counts(got, ref))

    def counts(self, name: str, counts: tuple[int, int, int]) -> None:
        """Add ``frame_counts`` taken before (of identical bytes)."""
        worst, n_bad, size = counts
        self.max_abs[name] = max(self.max_abs.get(name, 0), worst)
        off = self.off.setdefault(name, [0, 0])
        off[0] += n_bad
        off[1] += size

    def worse(self, name: str, value: float) -> None:
        self.worst[name] = max(self.worst.get(name, 0.0), float(value))

    def add(self, name: str, n: int = 1) -> None:
        self.count[name] = self.count.get(name, 0) + int(n)

    def numbers(self, names: list[str]) -> dict[str, float]:
        """``{name: value}`` for the ``names`` this cell compares:
        ``<x>_max_abs`` and ``<x>_off_share`` of frame comparisons,
        worst values, counts (0 where nothing was counted)."""
        out = {}
        for n in names:
            if n.endswith("_max_abs"):
                out[n] = self.max_abs.get(n[:-len("_max_abs")], 0)
            elif n.endswith("_off_share"):
                a, b = self.off.get(n[:-len("_off_share")], [0, 0])
                out[n] = a / b if b else 0.0
            elif n in self.worst:
                out[n] = self.worst[n]
            else:
                out[n] = self.count.get(n, 0)
        return out


def frame_counts(got: np.ndarray, ref: np.ndarray) -> tuple[int, int, int]:
    """``(largest |got - ref|, elements that differ, elements)``; a shape
    that differs makes every element wrong."""
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape:
        return 255, ref.size, ref.size
    bad = got != ref
    n_bad = int(np.count_nonzero(bad))
    worst = (int(np.abs(got[bad].astype(np.int32)
                        - ref[bad].astype(np.int32)).max()) if n_bad else 0)
    return worst, n_bad, int(ref.size)


def verdict(numbers: dict[str, float]) -> tuple[bool, dict]:
    """``(correct, {name: {"value": v, "limit": l}})``."""
    lim = limits()
    table = {n: {"value": v, "limit": lim[n]} for n, v in numbers.items()}
    return all(v <= lim[n] for n, v in numbers.items()), table


# -- stage-0 operators --------------------------------------------------------

def compare_stage0(t: Tally, op: str, got_raw: np.ndarray, got_items: set,
                   ref_raw: np.ndarray, ref_items: set,
                   positions: np.ndarray, spec: dict) -> None:
    """Per-frame outputs in units of the operator's threshold
    (``score_err``), and items that differ where the reference's score is
    not within ``NEAR_THRESHOLD`` of the threshold (``item_mismatch``).
    ``*_raw`` are Diff's unscaled pair changes or Motion's tile energies."""
    thr = R.THRESHOLD[op]
    got_raw = np.asarray(got_raw, np.float64)
    if got_raw.shape != ref_raw.shape:
        t.add("item_mismatch")
        return
    t.worse("score_err", np.abs(got_raw - ref_raw).max() / thr
            if got_raw.size else 0.0)
    for item in got_items ^ ref_items:
        if not _near_threshold(op, item, ref_raw, positions, spec, thr):
            t.add("item_mismatch")


def _near_threshold(op, item, ref_raw, positions, spec, thr) -> bool:
    b = R.bucket(positions, spec)
    band = NEAR_THRESHOLD * thr
    if op == "diff":
        scores = ref_raw / np.maximum(1, np.diff(positions))
        sel = b[1:] == item[1]
        return bool(np.any(np.abs(scores[sel] - thr) <= band))
    n = len(ref_raw)
    excess = ref_raw - np.median(ref_raw.reshape(n, -1), axis=1)[:, None,
                                                                 None]
    _tag, bk, iy, ix = item
    ty, tx = R.MOTION_GRID
    if not (0 <= iy < ty and 0 <= ix < tx):
        return False
    sel = b == bk
    return bool(np.any(np.abs(excess[sel, iy, ix] - thr) <= band))
