"""Plain float64 references for what the benchmark checks: the codec's
decode and encode, the fidelity conversion, and the two stage-0 operators
(Diff for query A, Motion for query B).

Written from the definitions (the 8x8 DCT-II with the quantization table
``q[i, j] = (1 + 1.5 (i + j)) * quant_scale``, DPCM from mid-gray within
each keyframe chunk, bilinear resize with an antialiasing triangle kernel,
the paper-ladder resolution and sampling rules, the operators' score
formulas and thresholds) in numpy float64.  Nothing here imports the
program under test or takes a table it made: the only input is the
stored bytes or the camera frames.
"""

from __future__ import annotations

import struct
import zlib

import msgpack
import numpy as np

BLOCK = 8
NOMINAL = 720          # paper name of the richest resolution rung

# operator constants, from the operator definitions
DIFF_THRESHOLD = 0.012
MOTION_THRESHOLD = 0.06
MOTION_GRID = (4, 6)


# -- basis and tables ---------------------------------------------------------

def dct_matrix() -> np.ndarray:
    """Orthonormal DCT-II basis ``D`` (rows are frequencies)."""
    i = np.arange(BLOCK)
    d = np.cos(np.pi * (2 * i[None, :] + 1) * i[:, None] / (2 * BLOCK))
    d[0] /= np.sqrt(2)
    return d * np.sqrt(2 / BLOCK)


def quant_steps(quant_scale: float) -> np.ndarray:
    i = np.arange(BLOCK)
    return (1 + 1.5 * (i[:, None] + i[None, :])) * quant_scale


# -- blobs ----------------------------------------------------------------------

def parse_blob(blob: bytes) -> tuple[dict, bytes]:
    """``[u32 header length][msgpack header][payload]``."""
    (hlen,) = struct.unpack_from("<I", blob, 0)
    return msgpack.unpackb(blob[4:4 + hlen]), blob[4 + hlen:]


def _inflate(coder: str, data: bytes) -> bytes:
    if coder == "zstd":
        import zstandard
        return zstandard.ZstdDecompressor().decompress(data)
    if coder == "zlib":
        return zlib.decompress(data)
    raise ValueError(f"unknown entropy coder {coder!r}")


def blob_symbols(blob: bytes) -> np.ndarray:
    """The quantized symbols of a coded (v2) blob, (n, h/8, w/8, 8, 8)
    int16, chunk by chunk."""
    hdr, payload = parse_blob(blob)
    if hdr["raw"] or hdr.get("v") != 2:
        raise ValueError("expects a coded v2 blob")
    n, h, w = hdr["n"], hdr["h"], hdr["w"]
    off = np.concatenate([[0], np.cumsum(hdr["spans"])]).astype(np.int64)
    parts = [np.frombuffer(_inflate(hdr["ec"], payload[off[c]:off[c + 1]]),
                           np.int16) for c in range(len(hdr["spans"]))]
    return np.concatenate(parts).reshape(n, h // BLOCK, w // BLOCK,
                                         BLOCK, BLOCK)


def decode_blob(blob: bytes) -> np.ndarray:
    """Every stored frame of a blob, (n, h, w) uint8: a RAW blob's bytes
    as they are, a coded blob dequantized, inverse-DCT'd and DPCM-added
    from mid-gray at each keyframe, clipped and rounded, in float64."""
    hdr, payload = parse_blob(blob)
    n, h, w = hdr["n"], hdr["h"], hdr["w"]
    if hdr["raw"]:
        return np.frombuffer(payload, np.uint8).reshape(n, h, w).copy()
    sym = blob_symbols(blob)
    d = dct_matrix()
    q = quant_steps(hdr["qs"])
    out = np.empty((n, h, w), np.uint8)
    pred = None
    for t in range(n):
        if t % hdr["k"] == 0:
            pred = np.full((h, w), 128.0)
        resid = d.T @ (sym[t] * q) @ d                  # (hb, wb, 8, 8)
        pred = np.clip(pred + resid.transpose(0, 2, 1, 3).reshape(h, w),
                       0, 255)
        out[t] = np.round(pred)
    return out


def encode_symbols(frames: np.ndarray, quant_scale: float,
                   keyframe: int) -> np.ndarray:
    """The symbols an exact encoder emits for (n, h, w) uint8 frames:
    per frame, the residual against the previous reconstruction (mid-gray
    at each keyframe), 8x8 DCT, quantized to the nearest step (ties to
    even); the reconstruction adds the dequantized inverse back and clips
    to [0, 255].  (n, h/8, w/8, 8, 8) int16."""
    n, h, w = frames.shape
    hb, wb = h // BLOCK, w // BLOCK
    d = dct_matrix()
    q = quant_steps(quant_scale)
    out = np.empty((n, hb, wb, BLOCK, BLOCK), np.int16)
    pred = None
    for t in range(n):
        if t % keyframe == 0:
            pred = np.full((h, w), 128.0)
        x = (frames[t] - pred).reshape(hb, BLOCK, wb, BLOCK)
        coef = d @ x.transpose(0, 2, 1, 3) @ d.T
        sym = np.round(coef / q)
        out[t] = sym
        resid = d.T @ (sym * q) @ d
        pred = np.clip(pred + resid.transpose(0, 2, 1, 3).reshape(h, w),
                       0, 255)
    return out


# -- fidelity ---------------------------------------------------------------

def _snap8(x: float) -> int:
    return max(8, int(round(x / 8)) * 8)


def resolve(spec: dict, fid: dict) -> tuple[int, int, int]:
    """(frames, height, width) of a segment in fidelity ``fid``
    (``quality``/``crop``/``resolution``/``sampling``) on the camera grid
    ``spec``: the resolution rung scales the camera's grid, the crop keeps
    the central part, both snapped to multiples of 8."""
    scale = fid["resolution"] / spec.get("nominal", NOMINAL)
    n_total = spec["fps"] * spec["segment_seconds"]
    return (max(1, round(n_total * fid["sampling"])),
            _snap8(spec["height"] * scale * fid["crop"]),
            _snap8(spec["width"] * scale * fid["crop"]))


def sample_positions(n_total: int, sampling: float) -> np.ndarray:
    """Original-timeline frames a sampling rate keeps: evenly spaced from
    frame 0, ``floor(i * n_total / n_keep)``."""
    n_keep = max(1, round(n_total * sampling))
    return np.floor(np.arange(n_keep) * (n_total / n_keep)).astype(np.int64)


def stored_rows(spec: dict, fid_from: dict, fid_to: dict) -> np.ndarray:
    """Rows of a segment stored at ``fid_from`` that realize ``fid_to``'s
    sampling: for each target position, the latest stored frame at or
    before it."""
    n_total = spec["fps"] * spec["segment_seconds"]
    n_from = resolve(spec, fid_from)[0]
    n_to = resolve(spec, fid_to)[0]
    if n_to == n_from:
        return np.arange(n_from)
    src = sample_positions(n_total, fid_from["sampling"])
    dst = sample_positions(n_total, fid_to["sampling"])
    return np.clip(np.searchsorted(src, dst, side="right") - 1, 0,
                   n_from - 1)


def resize_weights(n_in: int, n_out: int) -> np.ndarray:
    """(n_in, n_out) bilinear resize weights with antialiasing: output
    sample ``o`` sits at input coordinate ``(o + 0.5) * n_in / n_out -
    0.5``; a triangle kernel widened by the downscale factor, normalized
    to sum 1, and nothing for samples outside the input."""
    inv = n_in / n_out
    width = max(inv, 1.0)
    pos = (np.arange(n_out) + 0.5) * inv - 0.5
    x = np.abs(pos[None, :] - np.arange(n_in)[:, None]) / width
    wgt = np.maximum(0.0, 1.0 - x)
    tot = wgt.sum(axis=0, keepdims=True)
    wgt = np.where(np.abs(tot) > 1000 * np.finfo(np.float32).eps,
                   wgt / np.where(tot != 0, tot, 1), 0.0)
    inside = (pos >= -0.5) & (pos <= n_in - 0.5)
    return np.where(inside[None, :], wgt, 0.0)


def convert_frames(frames: np.ndarray, spec: dict, fid_from: dict,
                   fid_to: dict) -> np.ndarray:
    """Frames already sampled to ``fid_to``'s timeline, on ``fid_from``'s
    grid -> ``fid_to``'s grid: central crop by the crop ratio, bilinear
    resize, rounded and clipped to uint8."""
    _, h_to, w_to = resolve(spec, fid_to)
    x = np.asarray(frames, np.float64)
    rel = min(1.0, fid_to["crop"] / fid_from["crop"])
    if rel < 1.0:
        _, h, w = x.shape
        ch, cw = _snap8(h * rel), _snap8(w * rel)
        top, left = (h - ch) // 2, (w - cw) // 2
        x = x[:, top:top + ch, left:left + cw]
    _, h, w = x.shape
    if (h, w) != (h_to, w_to):
        wy, wx = resize_weights(h, h_to), resize_weights(w, w_to)
        x = np.einsum("nhw,hH,wW->nHW", x, wy, wx, optimize=True)
    return np.clip(np.round(x), 0, 255).astype(np.uint8)


# -- stage-0 operators ----------------------------------------------------------

def bucket(pos: np.ndarray, spec: dict) -> np.ndarray:
    """Half-second time buckets of original-timeline positions."""
    return np.asarray(pos) // max(1, spec["fps"] // 2)


def diff_change(frames: np.ndarray) -> np.ndarray:
    """Diff's per-pair output: the mean absolute change of [0, 1] pixels
    between consecutive consumed frames."""
    x = np.asarray(frames, np.float64) / 255.0
    return np.abs(x[1:] - x[:-1]).mean(axis=(1, 2))


def diff_items(change: np.ndarray, positions: np.ndarray,
               spec: dict) -> set:
    """An event in the bucket of a pair's later frame where the change per
    original-timeline frame of the pair's gap passes the threshold."""
    rate = change / np.maximum(1, np.diff(positions))
    hit = np.nonzero(rate > DIFF_THRESHOLD)[0]
    return {("evt", int(b)) for b in bucket(positions[hit + 1], spec)}


def motion_tiles(frames: np.ndarray) -> np.ndarray:
    """Motion: per frame, the mean edge energy (|vertical| + |horizontal|
    first differences of [0, 1] pixels) in each cell of a 4 x 6 grid."""
    ty, tx = MOTION_GRID
    x = np.asarray(frames, np.float64) / 255.0
    e = (np.abs(x[:, 1:, :-1] - x[:, :-1, :-1])
         + np.abs(x[:, :-1, 1:] - x[:, :-1, :-1]))
    n, h, w = e.shape
    hh, ww = (h // ty) * ty, (w // tx) * tx
    return e[:, :hh, :ww].reshape(n, ty, hh // ty, tx, ww // tx).mean(
        axis=(2, 4))


def motion_items(tiles: np.ndarray, positions: np.ndarray, shape: tuple,
                 crop: float, spec: dict) -> set:
    """Tiles whose energy exceeds the frame's median tile by the
    threshold, as (bucket, row, column) of the uncropped view's grid."""
    ty, tx = MOTION_GRID
    h, w = shape
    n = len(tiles)
    excess = tiles - np.median(tiles.reshape(n, -1), axis=1)[:, None, None]
    items = set()
    for t, iy, ix in zip(*np.nonzero(excess > MOTION_THRESHOLD)):
        y = (iy + 0.5) * h / ty - 0.5
        x = (ix + 0.5) * w / tx - 0.5
        ny = (y + 0.5) / h * crop + (1 - crop) / 2
        nx = (x + 0.5) / w * crop + (1 - crop) / 2
        items.add(("mot", int(bucket(positions[t], spec)), int(ny * ty),
                   int(nx * tx)))
    return items


THRESHOLD = {"diff": DIFF_THRESHOLD, "motion": MOTION_THRESHOLD}


def stage0(op: str, frames: np.ndarray, positions: np.ndarray,
           crop: float, spec: dict) -> tuple[np.ndarray, set]:
    """A stage-0 operator over one segment's consumed frames: its
    per-frame output (Diff's pair changes, Motion's tile energies) and
    its items."""
    if op == "diff":
        raw = diff_change(frames)
        return raw, diff_items(raw, positions, spec)
    if op == "motion":
        raw = motion_tiles(frames)
        return raw, motion_items(raw, positions, frames.shape[1:], crop,
                                 spec)
    raise KeyError(f"no reference for stage-0 operator {op!r}")
