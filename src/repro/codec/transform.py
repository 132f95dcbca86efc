"""Transform-coding primitives: 8x8 block DCT, quantization,
fidelity conversion (crop / resize / temporal sampling).

The DCT of an 8x8 block X is D @ X @ D.T with the orthonormal DCT-II basis D,
computed as explicit float32 multiply-adds (``apply8``).  The Pallas kernel
(src/repro/kernels/dct8) tiles frames into VMEM and fuses quantization; this
module is the pure-jnp implementation used as its oracle and as the CPU path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import COMPILES
from ..obs.trace import count as _count

# every data-path module that runs JAX programs imports this one, so the
# process's compile counter listens before any of them compiles
COMPILES.install()

BLOCK = 8


# ---------------------------------------------------------------------------
# Host <-> device crossings, counted in the tracer's per-thread tallies
# ---------------------------------------------------------------------------

def to_device(x, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``, counting the bytes a host array sends up
    as ``h2d_bytes``: numpy input is cast on the host first (to ``dtype``,
    canonicalized), so that buffer is what crosses.  An array already on
    the device crosses nothing."""
    if isinstance(x, np.ndarray):
        dt = jax.dtypes.canonicalize_dtype(x.dtype if dtype is None
                                           else dtype)
        _count("h2d_bytes", x.size * dt.itemsize)
    return jnp.asarray(x, dtype)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``, counting a device array's bytes as
    ``d2h_bytes``."""
    out = np.asarray(x)
    if isinstance(x, jax.Array):
        _count("d2h_bytes", out.nbytes)
    return out


# ---------------------------------------------------------------------------
# DCT backend selection: which implementation the codec's hot transforms
# (batched residual IDCT, encoder forward DCT) run on.  "auto" resolves to
# the fused Pallas kernels (repro.kernels.dct8) on TPU and the pure-jnp
# oracle elsewhere; "pallas" forces the kernels (interpret mode off-TPU,
# slow but bit-faithful — used by oracle tests), "jnp" forces the oracle.
# ---------------------------------------------------------------------------

_DCT_BACKENDS = ("auto", "jnp", "pallas")
_dct_backend = os.environ.get("REPRO_DCT_BACKEND", "auto")
if _dct_backend not in _DCT_BACKENDS:  # pragma: no cover - env misuse
    raise ValueError(f"REPRO_DCT_BACKEND must be one of {_DCT_BACKENDS}, "
                     f"got {_dct_backend!r}")


def set_dct_backend(name: str) -> None:
    """Select the codec transform backend: 'auto' | 'jnp' | 'pallas'."""
    global _dct_backend
    if name not in _DCT_BACKENDS:
        raise ValueError(f"backend must be one of {_DCT_BACKENDS}, got {name!r}")
    _dct_backend = name


def dct_backend() -> str:
    """The resolved backend ('jnp' or 'pallas') for the current platform."""
    if _dct_backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return _dct_backend


def dct_interpret() -> bool:
    """Whether a Pallas dispatch must run in interpret mode (off-TPU)."""
    return jax.default_backend() != "tpu"


@functools.cache
def dct_basis() -> np.ndarray:
    """Orthonormal 8x8 DCT-II basis matrix D (D @ D.T = I)."""
    k = np.arange(BLOCK)[:, None]
    n = np.arange(BLOCK)[None, :]
    d = np.cos(np.pi * (2 * n + 1) * k / (2 * BLOCK))
    d[0] *= 1.0 / np.sqrt(2)
    d *= np.sqrt(2.0 / BLOCK)
    return d.astype(np.float32)


@functools.cache
def quant_table() -> np.ndarray:
    """JPEG-like base quantization table scaled to unit DC step: higher
    frequencies quantized more coarsely."""
    i = np.arange(BLOCK)[:, None]
    j = np.arange(BLOCK)[None, :]
    return (1.0 + (i + j) * 1.5).astype(np.float32)


def to_blocks(frames: jnp.ndarray) -> jnp.ndarray:
    """(n, h, w) -> (n, h//8, w//8, 8, 8)."""
    n, h, w = frames.shape
    x = frames.reshape(n, h // BLOCK, BLOCK, w // BLOCK, BLOCK)
    return x.transpose(0, 1, 3, 2, 4)


def from_blocks(blocks: jnp.ndarray) -> jnp.ndarray:
    """(n, hb, wb, 8, 8) -> (n, h, w)."""
    n, hb, wb, _, _ = blocks.shape
    return blocks.transpose(0, 1, 3, 2, 4).reshape(n, hb * BLOCK, wb * BLOCK)


def split12(x):
    """``x == hi + lo`` exactly, each half with at most 12 significant bits
    (``hi`` is ``x`` with its low 12 mantissa bits cleared), so the product
    of two halves is exact in float32.  numpy in, numpy out."""
    if isinstance(x, np.ndarray):
        hi = (x.astype(np.float32).view(np.int32) & -4096).view(np.float32)
    else:
        bits = jax.lax.bitcast_convert_type(x, jnp.int32) & -4096
        hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi, x - hi


def split_mul(m_hi, m_lo, x_hi, x_lo):
    """``m * x`` from split halves: four exact partial products summed in
    a fixed order.  Every product is exact, so whether a backend fuses a
    multiply into the following add (FMA) cannot change a bit."""
    return ((m_hi * x_hi + m_hi * x_lo) + m_lo * x_hi) + m_lo * x_lo


def apply8(x: jnp.ndarray, m: np.ndarray, axis: int) -> jnp.ndarray:
    """Apply the 8x8 matrix ``m`` along ``axis`` (of size 8):
    ``out[.., i, ..] = sum_j m[i, j] * x[.., j, ..]``.

    Written as float32 multiply-adds in ascending ``j`` (``split_mul``
    products) rather than as a dot, so the result is the same bit for bit
    at every array size and on every backend: XLA:CPU picks
    differently-ordered dot kernels by shape and fuses multiply-adds into
    FMAs case by case, and a TPU dot at default precision is a single
    bfloat16 pass.  The Pallas kernels (``repro.kernels.dct8``) run the
    same sequence per element, so the two codec backends agree bit for
    bit."""
    shape = [1] * x.ndim
    shape[axis] = BLOCK
    m_hi, m_lo = split12(np.asarray(m, np.float32))
    x_hi, x_lo = split12(x)
    acc = None
    for j in range(BLOCK):
        term = split_mul(jnp.asarray(m_hi[:, j]).reshape(shape),
                         jnp.asarray(m_lo[:, j]).reshape(shape),
                         jax.lax.index_in_dim(x_hi, j, axis),
                         jax.lax.index_in_dim(x_lo, j, axis))
        acc = term if acc is None else acc + term
    return acc


def dct2(blocks: jnp.ndarray) -> jnp.ndarray:
    """Forward 2D DCT over trailing (8, 8) dims: D @ X @ D.T (rows, then
    columns)."""
    d = dct_basis()
    return apply8(apply8(blocks, d, -2), d, -1)


def idct2(coefs: jnp.ndarray) -> jnp.ndarray:
    """Inverse 2D DCT over trailing (8, 8) dims: D.T @ C @ D."""
    dt = dct_basis().T
    return apply8(apply8(coefs, dt, -2), dt, -1)


def quantize(coefs: jnp.ndarray, quant_scale: float) -> jnp.ndarray:
    q = jnp.asarray(quant_table()) * quant_scale
    return jnp.round(coefs / q).astype(jnp.int16)


def dequantize(symbols: jnp.ndarray, quant_scale: float) -> jnp.ndarray:
    q = jnp.asarray(quant_table()) * quant_scale
    return symbols.astype(jnp.float32) * q


def symbols_to_residuals(symbols: jnp.ndarray,
                         quant_scale: float) -> jnp.ndarray:
    """Fused dequantize + IDCT + de-blocking for a frame stack:
    (n, hb, wb, 8, 8) int16 -> (n, h, w) float32.

    The decode hot path.  Bit-exact with
    ``from_blocks(idct2(dequantize(symbols, qs)))``, but the int16 symbols
    are de-blocked first (pixel layout, ``(n, hb, i, wb, l)``), so no
    float32 stack is ever transposed."""
    n, hb, wb = symbols.shape[:3]
    q = jnp.asarray(quant_table()) * quant_scale
    coef = symbols.transpose(0, 1, 3, 2, 4).astype(jnp.float32) \
        * q[:, None, :]
    dt = dct_basis().T
    out = apply8(apply8(coef, dt, 2), dt, 4)
    return out.reshape(n, hb * BLOCK, wb * BLOCK)


def frames_to_symbols(frames: jnp.ndarray, quant_scale: float) -> jnp.ndarray:
    """Fused blocking + DCT + quantize for a frame stack:
    (n, h, w) float32 -> (n, hb, wb, 8, 8) int16 — the encode-side twin of
    ``symbols_to_residuals`` (bit-exact with
    ``quantize(dct2(to_blocks(frames)), qs)``)."""
    n, h, w = frames.shape
    d = dct_basis()
    x = frames.reshape(n, h // BLOCK, BLOCK, w // BLOCK, BLOCK)
    coef = apply8(apply8(x, d, 2), d, 4)
    q = jnp.asarray(quant_table()) * quant_scale
    sym = jnp.round(coef / q[:, None, :]).astype(jnp.int16)
    return sym.transpose(0, 1, 3, 2, 4)


# ---------------------------------------------------------------------------
# Fidelity conversion
# ---------------------------------------------------------------------------

def sample_indices(n_total: int, sampling: float) -> np.ndarray:
    """Deterministic frame-sampling index set (monotone in ``sampling``:
    richer sampling consumes a superset-density of the timeline)."""
    n_keep = max(1, round(n_total * sampling))
    return np.floor(np.arange(n_keep) * (n_total / n_keep)).astype(np.int64)


def center_crop(frames: jnp.ndarray, crop: float) -> jnp.ndarray:
    """Central crop to ``crop`` fraction on both axes, snapped to x8."""
    if crop >= 1.0:
        return frames
    n, h, w = frames.shape
    ch = max(8, int(round(h * crop / 8)) * 8)
    cw = max(8, int(round(w * crop / 8)) * 8)
    top, left = (h - ch) // 2, (w - cw) // 2
    return frames[:, top:top + ch, left:left + cw]


@functools.partial(jax.jit, static_argnames=("h", "w"))
def _resize(frames: jnp.ndarray, h: int, w: int) -> jnp.ndarray:
    return jax.image.resize(frames, (frames.shape[0], h, w), method="bilinear")


def resize(frames: jnp.ndarray, h: int, w: int) -> jnp.ndarray:
    if frames.shape[1:] == (h, w):
        return frames
    return _resize(frames.astype(jnp.float32), h, w)


@jax.jit
def _quality_roundtrip(frames_f32: jnp.ndarray, quant_scale: jnp.ndarray):
    blocks = to_blocks(frames_f32)
    sym = quantize(dct2(blocks), quant_scale)
    return from_blocks(idct2(dequantize(sym, quant_scale)))


def apply_quality(frames_u8, quant_scale: float):
    """Intra-frame quantization roundtrip — the image-quality knob's effect on
    pixels, used when materializing consumption-fidelity samples for
    profiling (full DPCM coding adds only second-order differences)."""
    if quant_scale <= 1.0:
        return jnp.asarray(frames_u8, jnp.uint8)
    x = _quality_roundtrip(jnp.asarray(frames_u8, jnp.float32),
                           jnp.float32(quant_scale))
    return jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)


def materialize(frames_u8, cf, spec, src=None):
    """Ingest-fidelity frames -> consumption-fidelity frames (sampling, crop,
    resolution, then image-quality loss)."""
    from ..core.knobs import FidelityOption
    src = src or FidelityOption()
    out = convert_fidelity(frames_u8, src, cf, spec)
    return apply_quality(out, cf.quant_scale)


def temporal_indices(f_from, f_to, spec) -> np.ndarray:
    """Indices into a segment stored at fidelity ``f_from`` that realize the
    (sparser) sampling of ``f_to`` — the stored frames nearest to the target
    timeline points.  These drive chunk-skip decoding."""
    n_from, _, _ = spec.resolve(f_from)
    n_to, _, _ = spec.resolve(f_to)
    if n_to == n_from:
        return np.arange(n_from)
    src_pos = sample_indices(spec.frames_per_segment, f_from.sampling)
    dst_pos = sample_indices(spec.frames_per_segment, f_to.sampling)
    nearest = np.searchsorted(src_pos, dst_pos, side="right") - 1
    return np.clip(nearest, 0, n_from - 1)


def spatial_convert(frames, f_from, f_to, spec):
    """Crop + resize a (already temporally sampled) frame stack from
    ``f_from``'s grid to ``f_to``'s.  Returns uint8."""
    _, h_to, w_to = spec.resolve(f_to)
    rel_crop = f_to.crop / f_from.crop
    x = center_crop(to_device(frames, jnp.float32), min(1.0, rel_crop))
    x = resize(x, h_to, w_to)
    return jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)


def convert_fidelity(frames_u8, f_from, f_to, spec):
    """Convert a segment from fidelity ``f_from`` to ``f_to``.

    ``f_from`` must be richer-than-or-equal ``f_to`` (R1).  Applies temporal
    re-sampling, central re-crop and spatial resize.  Image-quality loss is a
    coding-time effect and needs no conversion here (a higher-quality source
    simply over-delivers).  Returns uint8 frames shaped per spec.resolve(f_to).
    """
    if not f_from.richer_eq(f_to):
        raise ValueError(f"fidelity {f_from.name()} cannot serve {f_to.name()}")
    n_from, _, _ = spec.resolve(f_from)
    frames = to_device(frames_u8)
    if frames.shape[0] != n_from:
        raise ValueError(f"segment has {frames.shape[0]} frames, spec says {n_from}")
    frames = frames[temporal_indices(f_from, f_to, spec)]
    return spatial_convert(frames, f_from, f_to, spec)
