"""Keeps what the timed path produces, for the check after the window.

Wrappers around four of the program's calls record their results as the
window runs them, untouched: the store's ``decode_for`` (decoded frames on
the storage grid), the planner's ``fetch`` (frames converted to a
consumption format), and the stage-0 operators (Diff's pair changes and
Motion's tile energies, with the items each batched call returned).  The
wrappers hold references, copy nothing, and keep frames only for a sample
of segments chosen from the seed.  Batched operator calls are split back
into segments by a fingerprint of each segment's frames, which the fetch
wrapper takes of every stage-0 conversion, and keeps for every segment
it converts (``read``).  A stage-0 call whose
per-frame outputs the wrappers did not see is counted (``unread``), so
that a program that computes them elsewhere fails the check rather than
passing it unread.
"""

from __future__ import annotations

import hashlib
import threading

import numpy as np


def fingerprint(frames: np.ndarray) -> bytes:
    """A cheap digest of a frame stack: its shape and a strided sample of
    its pixels."""
    f = np.asarray(frames)
    step = max(1, len(f) // 5)
    sample = np.ascontiguousarray(f[::step, ::23, ::29])
    h = hashlib.blake2b(repr(f.shape).encode(), digest_size=16)
    h.update(sample.tobytes())
    return h.digest()


class Recorder:
    """``stage0`` maps each stage-0 consumption format (the program's
    ``FidelityOption``) to its operator's name and its frames per segment;
    ``sample`` is the set of ``(stream, seg)`` whose frames are kept."""

    def __init__(self, stage0: dict, sample: set, bucket_frames: int):
        self.stage0 = stage0
        self.sample = set(sample)
        self.bucket_frames = bucket_frames
        self._mu = threading.Lock()
        self._tls = threading.local()
        # (stream, seg, sf) -> [(want, frames)], one per distinct want
        self.decodes: dict[tuple, list] = {}
        self.converts: dict[tuple, np.ndarray] = {}  # (stream, seg, cf) -> frames
        self.where: dict[bytes, tuple] = {}     # fingerprint -> (stream, seg, cf)
        # (stream, seg, cf) -> fingerprints of every conversion fetched
        self.read: dict[tuple, set] = {}
        self.blocks: list[tuple] = []           # (cf, fingerprint, raw, items)
        # stage-0 calls whose per-frame outputs were not seen
        self.unread = 0
        self._undo: list = []

    # -- across processes ----------------------------------------------------
    def state(self) -> dict:
        """What the wrappers kept, to be merged into another process's
        recorder (``merge``)."""
        with self._mu:
            return {"decodes": self.decodes, "converts": self.converts,
                    "where": self.where, "read": self.read,
                    "blocks": self.blocks, "unread": self.unread}

    def merge(self, state: dict) -> None:
        """Add another recorder's ``state``: a sharded cell's shards each
        keep their own streams'."""
        with self._mu:
            self.decodes.update(state["decodes"])
            self.converts.update(state["converts"])
            for fp, where in state["where"].items():
                self.where.setdefault(fp, where)
            for key, fps in state["read"].items():
                self.read.setdefault(key, set()).update(fps)
            self.blocks.extend(state["blocks"])
            self.unread += state["unread"]

    # -- installing ----------------------------------------------------------
    def install(self, store=None, server=None) -> "Recorder":
        from repro.analytics import operators as ops
        if store is not None:
            self._wrap_attr(store, "decode_for", self._decode_for)
        if server is not None:
            self._wrap_attr(server.planner, "fetch", self._fetch)
        for fn in ("_diff_scores", "_motion_tiles"):
            self._wrap_attr(ops, fn, self._stash)
        for op in sorted({op for op, _n in self.stage0.values()}):
            self._wrap_attr(ops.OPERATORS[op], "detect", self._detect)
        return self

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()

    def _wrap_attr(self, obj, name: str, make) -> None:
        orig = getattr(obj, name)
        had_own = name in getattr(obj, "__dict__", {})
        setattr(obj, name, make(orig))

        def undo():
            if had_own:
                setattr(obj, name, orig)
            else:
                delattr(obj, name)
        self._undo.append(undo)

    # -- wrappers --------------------------------------------------------------
    def _decode_for(self, orig):
        def decode_for(stream, seg, sf_id, want):
            frames, cost = orig(stream, seg, sf_id, want)
            if (stream, seg) in self.sample:
                want = np.asarray(want)
                with self._mu:
                    kept = self.decodes.setdefault((stream, seg, sf_id), [])
                    if not any(np.array_equal(want, w) for w, _f in kept):
                        kept.append((want, frames))
            return frames, cost
        return decode_for

    def _fetch(self, orig):
        def fetch(stream, seg, sf_id, cf):
            out, cost = orig(stream, seg, sf_id, cf)
            if cf in self.stage0:
                fp = fingerprint(out)
                with self._mu:
                    self.where.setdefault(fp, (stream, seg, cf))
                    self.read.setdefault((stream, seg, cf), set()).add(fp)
                    if (stream, seg) in self.sample:
                        self.converts.setdefault((stream, seg, cf), out)
            return out, cost
        return fetch

    def _stash(self, orig):
        def scores(*args):
            out = orig(*args)
            self._tls.out = out
            return out
        return scores

    def _detect(self, orig):
        def detect(frames_u8, cf, spec, positions=None):
            self._tls.out = None
            items = orig(frames_u8, cf, spec, positions=positions)
            raw = self._tls.out
            self._tls.out = None
            if cf in self.stage0:
                kept = (self._split(cf, frames_u8, np.asarray(positions),
                                    np.asarray(raw), items)
                        if raw is not None and positions is not None else 0)
                if not kept:
                    with self._mu:
                        self.unread += 1
            return items
        return detect

    def _split(self, cf, frames, positions, raw, items) -> int:
        """One batched call -> per-segment blocks of ``n`` frames: the
        block's per-frame outputs (Diff: its ``n - 1`` inner pairs) and
        its items moved back to the segment's own buckets.  Returns the
        number of blocks kept."""
        op, n = self.stage0[cf]
        frames = np.asarray(frames)
        out = []
        for k in range(len(frames) // n):
            blk = frames[k * n:(k + 1) * n]
            if not blk.any():
                break  # padding rows: zeros after the last segment
            pos = positions[k * n:(k + 1) * n]
            buckets = set((pos // self.bucket_frames).tolist())
            off = int(pos[0]) // self.bucket_frames
            mine = {(it[0], int(it[1]) - off) + tuple(it[2:])
                    for it in items if int(it[1]) in buckets}
            r = (raw[k * n:k * n + n - 1] if op == "diff"
                 else raw[k * n:(k + 1) * n])
            out.append((cf, fingerprint(blk), np.array(r), mine))
        with self._mu:
            self.blocks.extend(out)
        return len(out)
