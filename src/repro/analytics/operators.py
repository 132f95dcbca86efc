"""The six analytics operators (paper Fig. 2), implemented as JAX tensor
programs over raw frames.

Query A (car detection):      Diff -> S-NN -> NN
Query B (license recognition): Motion -> License -> OCR

Each operator consumes frames at some consumption fidelity and emits a set of
hashable *items* in a fidelity-independent space (time buckets on the original
timeline; positions normalized to the uncropped full view).  Accuracy is the
paper's F1 of an operator's items against its own items on full-fidelity
video.  Consumption *cost* is measured wall time (the profiler times the
jitted compute); image quality affects items only (observation O2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..codec import transform as T
from ..core.knobs import FidelityOption, IngestSpec
from .scene import digit_glyphs

Item = tuple


def _bucket(pos: int, spec: IngestSpec) -> int:
    return int(pos) // max(1, spec.fps // 2)


def _positions(cf: FidelityOption, spec: IngestSpec) -> np.ndarray:
    """Original-timeline positions of the consumed frames."""
    return T.sample_indices(spec.frames_per_segment, cf.sampling)


def _to_norm(y, x, h, w, crop):
    """Map pixel coords in a cropped/resized frame to full-view [0,1]^2."""
    ny = (np.asarray(y) + 0.5) / h * crop + (1 - crop) / 2
    nx = (np.asarray(x) + 0.5) / w * crop + (1 - crop) / 2
    return ny, nx


def _conv(x, kernel: np.ndarray):
    """NHW x (kh, kw) -> (n, h', w') valid correlation with a constant
    ``kernel``, as float32 multiply-adds of shifted views: a loop over
    the kernel rows, each adding its ``kw`` column taps, so one band of
    ``x`` and the accumulator are all the memory it takes (callers with
    several kernels combine their maps one kernel at a time).  Not
    ``lax.conv``: a one-channel conv is a poor fit for the TPU's matrix
    unit, where full float32 (``Precision.HIGHEST``) takes minutes to
    compile for a v5e even at 8x96x160, and default precision is one
    bfloat16 pass, which moves the scores the detectors threshold.
    Elementwise float32 runs at full float32 on every backend and
    compiles in seconds."""
    kh, kw = kernel.shape
    n, h, w = x.shape
    ho, wo = h - kh + 1, w - kw + 1
    k = jnp.asarray(kernel, x.dtype)

    def add_row(i, acc):
        band = jax.lax.dynamic_slice_in_dim(x, i, ho, axis=1)
        taps = [band[:, :, j:j + wo] * k[i, j] for j in range(kw)]
        return acc + functools.reduce(jnp.add, taps)

    return jax.lax.fori_loop(0, kh, add_row,
                             jnp.zeros((n, ho, wo), x.dtype))


# ---------------------------------------------------------------------------
# Operator base
# ---------------------------------------------------------------------------

class Operator:
    name: str = "op"

    def detect(self, frames_u8: np.ndarray, cf: FidelityOption,
               spec: IngestSpec, positions: np.ndarray | None = None
               ) -> set[Item]:
        """``positions`` gives the original-timeline index of each
        supplied frame (defaults to the full consumed set implied by
        ``cf.sampling``); cascades pass activated subsets."""
        raise NotImplementedError

    def __repr__(self):
        return f"<op {self.name}>"


# ---------------------------------------------------------------------------
# Diff: frame-difference event detector (cheapest)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def _diff_scores(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.abs(x[1:] - x[:-1]), axis=(1, 2))


class Diff(Operator):
    name = "diff"
    threshold = 0.012  # mean-abs-diff rate per original-timeline frame

    def detect(self, frames_u8, cf, spec, positions=None):
        x = T.to_device(frames_u8, jnp.float32) / 255.0
        if x.shape[0] < 2:
            return set()
        pos = _positions(cf, spec) if positions is None else positions
        gaps = np.maximum(1, np.diff(pos))
        scores = T.to_host(_diff_scores(x)) / gaps  # per-frame change rate
        return {("evt", _bucket(pos[i + 1], spec))
                for i in np.nonzero(scores > self.threshold)[0]}


# ---------------------------------------------------------------------------
# Motion: tiled foreground/texture detector (works single-frame)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("ty", "tx"))
def _motion_tiles(x: jnp.ndarray, ty: int, tx: int) -> jnp.ndarray:
    gy = jnp.abs(x[:, 1:, :-1] - x[:, :-1, :-1])
    gx = jnp.abs(x[:, :-1, 1:] - x[:, :-1, :-1])
    e = gy + gx
    n, h, w = e.shape
    hh, ww = (h // ty) * ty, (w // tx) * tx
    e = e[:, :hh, :ww].reshape(n, ty, hh // ty, tx, ww // tx)
    return e.mean(axis=(2, 4))


class Motion(Operator):
    name = "motion"
    threshold = 0.06  # tile energy in excess of the frame's median tile
    grid = (4, 6)

    def detect(self, frames_u8, cf, spec, positions=None):
        ty, tx = self.grid
        x = T.to_device(frames_u8, jnp.float32) / 255.0
        n, h, w = x.shape
        if h < ty or w < tx:
            return set()
        tiles = T.to_host(_motion_tiles(x, ty, tx))
        # excess over the frame's median tile: robust to the uniform noise /
        # smoothing floor (quality knob), sensitive to car-specific edges
        med = np.median(tiles.reshape(n, -1), axis=1)[:, None, None]
        tiles = tiles - med
        pos = _positions(cf, spec) if positions is None else positions
        items = set()
        for t, iy, ix in zip(*np.nonzero(tiles > self.threshold)):
            cy, cx = _to_norm((iy + 0.5) * h / ty - 0.5, (ix + 0.5) * w / tx - 0.5,
                              h, w, cf.crop)
            items.add(("mot", _bucket(pos[t], spec),
                       int(cy * ty), int(cx * tx)))
        return items


# ---------------------------------------------------------------------------
# S-NN: small fixed convnet (shallow AlexNet stand-in)
# ---------------------------------------------------------------------------

@functools.cache
def _snn_kernels() -> np.ndarray:
    k = np.zeros((3, 5, 5), np.float32)
    k[0, 2, :] = 1.0; k[0, 0, :] = -0.5; k[0, 4, :] = -0.5       # horiz edge
    k[1, :, 2] = 1.0; k[1, :, 0] = -0.5; k[1, :, 4] = -0.5       # vert edge
    k[2] = -1 / 25.; k[2, 1:4, 1:4] = (25 - 9) / (25. * 9)       # center-surround
    return k


@functools.partial(jax.jit, static_argnames=("gy", "gx"))
def _snn_scores(x: jnp.ndarray, gy: int, gx: int) -> jnp.ndarray:
    # energy over channels
    a = functools.reduce(jnp.add, [jnp.square(jax.nn.relu(_conv(x, k)))
                                   for k in _snn_kernels()])
    n, h, w = a.shape
    hh, ww = (h // gy) * gy, (w // gx) * gx
    a = a[:, :hh, :ww].reshape(n, gy, hh // gy, gx, ww // gx)
    return a.mean(axis=(2, 4))


class SNN(Operator):
    name = "snn"
    threshold = 0.050
    grid = (3, 5)

    def detect(self, frames_u8, cf, spec, positions=None):
        gy, gx = self.grid
        x = T.to_device(frames_u8, jnp.float32) / 255.0
        n, h, w = x.shape
        if h < gy + 5 or w < gx + 5:
            return set()
        cells = T.to_host(_snn_scores(x, gy, gx))
        pos = _positions(cf, spec) if positions is None else positions
        items = set()
        for t, iy, ix in zip(*np.nonzero(cells > self.threshold)):
            cy, cx = _to_norm((iy + 0.5) * h / gy - 0.5, (ix + 0.5) * w / gx - 0.5,
                              h, w, cf.crop)
            items.add(("car", _bucket(pos[t], spec), int(cy * gy), int(cx * gx)))
        return items


# ---------------------------------------------------------------------------
# NN: multi-scale template detector (the expensive deep model stand-in)
# ---------------------------------------------------------------------------

@functools.cache
def _nn_templates() -> np.ndarray:
    """4 zero-mean 12x12 car-part templates."""
    t = np.zeros((4, 12, 12), np.float32)
    t[0, 2:10, 1:11] = 1.0                       # bright body
    t[1, 3:6, 1:11] = -1.0; t[1, 7:10, 1:11] = 1.0   # dark window over body
    t[2, :, 2:4] = 1.0; t[2, :, 8:10] = -1.0     # vertical edge pair
    t[3, 4:8, 2:10] = 1.0; t[3, 5:7, 3:9] = -1.2  # plate-ish ring
    t -= t.mean(axis=(1, 2), keepdims=True)
    t /= np.linalg.norm(t, axis=(1, 2), keepdims=True)
    return t


@functools.partial(jax.jit, static_argnames=("h2", "w2"))
def _nn_scale_scores(x: jnp.ndarray, h2: int, w2: int) -> jnp.ndarray:
    xs = jax.image.resize(x, (x.shape[0], h2, w2), "bilinear")
    xs = xs - xs.mean(axis=(1, 2), keepdims=True)
    # (n, h', w') best-template score
    return functools.reduce(jnp.maximum,
                            [_conv(xs, t) for t in _nn_templates()])


class NN(Operator):
    name = "nn"
    threshold = 1.7
    scales = (1.0, 2 / 3, 1 / 2)
    qgrid = 8

    def detect(self, frames_u8, cf, spec, positions=None):
        x = T.to_device(frames_u8, jnp.float32) / 255.0
        n, h, w = x.shape
        pos = _positions(cf, spec) if positions is None else positions
        items = set()
        for si, s in enumerate(self.scales):
            h2, w2 = max(14, int(h * s)), max(14, int(w * s))
            sc = T.to_host(_nn_scale_scores(x, h2, w2))
            for t, iy, ix in zip(*np.nonzero(sc > self.threshold)):
                cy, cx = _to_norm(iy + 6, ix + 6, h2, w2, cf.crop)
                q = self.qgrid
                items.add(("carbox", _bucket(pos[t], spec),
                           int(cy * q), int(cx * q), si))
        return items


# ---------------------------------------------------------------------------
# License: plate-region detector (bright box + dense dark edges)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def _license_scores(x: jnp.ndarray) -> jnp.ndarray:
    bright = (x > 0.80).astype(x.dtype)
    gx = jnp.abs(jnp.diff(x, axis=2))
    edge = (gx > 0.25).astype(x.dtype)
    box = np.full((5, 11), 1 / (5 * 11), np.float32)
    b = _conv(bright, box)
    e = _conv(edge, box)[:, :, :-1]
    hh = min(b.shape[1], e.shape[1]); ww = min(b.shape[2], e.shape[2])
    return b[:, :hh, :ww] * e[:, :hh, :ww]


class License(Operator):
    name = "license"
    threshold = 0.035
    qgrid = 12

    def score_map(self, frames_u8) -> np.ndarray:
        x = T.to_device(frames_u8, jnp.float32) / 255.0
        if x.shape[1] < 7 or x.shape[2] < 13:
            return np.zeros((x.shape[0], 1, 1), np.float32)
        return T.to_host(_license_scores(x))

    def detect(self, frames_u8, cf, spec, positions=None):
        sc = self.score_map(frames_u8)
        n, h, w = np.asarray(frames_u8).shape
        pos = _positions(cf, spec) if positions is None else positions
        items = set()
        for t in range(sc.shape[0]):
            ys, xs = np.nonzero(sc[t] > self.threshold)
            if len(ys) == 0:
                continue
            # cluster hits to cell grid
            cy, cx = _to_norm(ys + 2, xs + 5, h, w, cf.crop)
            q = self.qgrid
            for a, b in set(zip((cy * q).astype(int), (cx * q).astype(int))):
                items.add(("plate", _bucket(pos[t], spec), int(a), int(b)))
        return items


# ---------------------------------------------------------------------------
# OCR: digit reading inside detected plate regions
# ---------------------------------------------------------------------------

class OCR(Operator):
    name = "ocr"
    conf = 0.55
    _detector = License()

    def detect(self, frames_u8, cf, spec, positions=None):
        frames = np.asarray(frames_u8, np.float32) / 255.0
        sc = self._detector.score_map(frames_u8)
        n, h, w = frames.shape
        pos = _positions(cf, spec) if positions is None else positions
        glyphs = np.asarray(digit_glyphs())
        glyphs = glyphs - glyphs.mean(axis=(1, 2), keepdims=True)
        # plate canonical size at ingest scale
        items = set()
        for t in range(n):
            flat = sc[t].ravel()
            if flat.size == 0:
                continue
            order = np.argsort(flat)[::-1][:3]
            for o in order:
                if flat[o] <= self._detector.threshold:
                    break
                iy, ix = np.unravel_index(o, sc[t].shape)
                py, px = iy + 2, ix + 5  # plate center-ish in frame coords
                # extract patch scaled to canonical 9x26 plate
                ph = max(4, int(round(9 * h / 96)))
                pw = max(8, int(round(26 * w / 160)))
                y0, x0 = py - ph // 2, px - pw // 2
                if y0 < 0 or x0 < 0 or y0 + ph > h or x0 + pw > w:
                    continue
                patch = frames[t, y0:y0 + ph, x0:x0 + pw]
                patch = np.asarray(T.resize(jnp.asarray(patch[None]), 9, 26))[0]
                digits, confs = [], []
                for slot in range(4):
                    cell = patch[1:8, 1 + slot * 6:6 + slot * 6]
                    cell = 1.0 - cell  # digits are dark on white
                    cell = cell - cell.mean()
                    nrm = np.linalg.norm(cell) + 1e-6
                    corr = (glyphs * cell).sum(axis=(1, 2)) / (
                        nrm * (np.linalg.norm(glyphs, axis=(1, 2)) + 1e-6))
                    digits.append(int(np.argmax(corr)))
                    confs.append(float(np.max(corr)))
                if np.mean(confs) > self.conf:
                    items.add(("ocr", _bucket(pos[t], spec),
                               "".join(map(str, digits))))
        return items


OPERATORS: dict[str, Operator] = {
    op.name: op for op in (Diff(), Motion(), SNN(), NN(), License(), OCR())
}
