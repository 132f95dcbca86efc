"""Segment-level encode / decode.

An encoded segment is a sequence of *chunks* ("group of pictures"): each chunk
begins with an intra-coded frame (predicted from mid-gray) followed by
delta-coded frames (predicted from the previous *reconstructed* frame, DPCM
style, so there is no drift between encoder and decoder).  Chunks decode
independently — sparse frame sampling therefore skips whole chunks
(paper Fig. 3b).  Quantized DCT symbols are entropy-coded with zstd whose
level realizes the *speed step* knob (paper Fig. 3a); when the optional
``zstandard`` module is absent we fall back to stdlib ``zlib`` and record
the entropy coder in the blob header (``"ec"``), so blobs stay
self-describing and either coder can read its own output.

Blob layout (common): ``[u32 header_len][msgpack header][payload bytes]``.

Two header-versioned payload formats coexist (``"v"`` field; absent = v1):

* **v1** — one entropy-coded stream over the whole segment's symbols.
  Any decode, however sparse, must decompress the entire payload.
* **v2** (default) — each chunk is entropy-coded *independently* and the
  header records per-chunk compressed byte lengths (``"spans"``), VSS-style
  chunk-granular physical layout.  Chunk-skip then skips decompression and
  payload *bytes*, not just transform work: a 1/30-sparse read touches
  ``header + spans[c]`` bytes for the one chunk ``c`` it needs.  Symbols of
  a short tail chunk are stored unpadded (``n`` and ``k`` determine each
  chunk's frame count).

Decoding is *batched*: all wanted chunks' residuals are reconstructed in a
single jit dispatch (``_decode_chunks`` — dequantize + IDCT over every
frame at once, zero-padded to the keyframe interval), then a cheap
sequential add+clip scan runs over the precomputed residuals.  Per-frame
float ops and their order are identical to the per-chunk reference scan
(``decode_segment_scan``), so results are bit-exact by construction.  The
dequantize+IDCT (and the encoder's forward DCT) route through the fused
Pallas kernels in ``repro.kernels.dct8`` when the transform backend
resolves to ``"pallas"`` (see ``transform.set_dct_backend``); the pure-jnp
path is the oracle and the CPU default.
"""

from __future__ import annotations

import functools
import struct
import zlib

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

try:
    import zstandard
except ImportError:  # pragma: no cover - exercised on bare interpreters
    zstandard = None

from . import transform as T
# module import, not names: importing the kernel module first must not
# trip over this package's import of it (dct8 -> codec.transform -> here)
from ..kernels.dct8 import dct8 as _dct8
from ..obs.trace import span as _span

_MAGIC = "tpucodec-v1"

#: Blob format written by :func:`encode_segment` when ``version`` is None.
DEFAULT_VERSION = 2


def _compress(payload: bytes, level: int) -> tuple[str, bytes]:
    """Entropy-code with zstd when available, else zlib.  Returns the coder
    tag recorded in the header alongside the compressed payload."""
    if zstandard is not None:
        return "zstd", zstandard.ZstdCompressor(level=level).compress(payload)
    return "zlib", zlib.compress(payload, min(9, max(1, level)))


def _decompress(coder: str, payload: bytes) -> bytes:
    if coder == "zstd":
        if zstandard is None:
            raise RuntimeError(
                "blob was zstd-coded but the zstandard module is unavailable")
        return zstandard.ZstdDecompressor().decompress(payload)
    if coder == "zlib":
        return zlib.decompress(payload)
    raise ValueError(f"unknown entropy coder {coder!r}")


# ---------------------------------------------------------------------------
# Chunk coding (jitted; tail chunks are padded to the keyframe interval
# before encode and sliced after, so there is ONE compile per (k, hb, wb)
# regardless of segment length)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def _encode_chunk(frames_f32: jnp.ndarray, quant_scale: jnp.ndarray,
                  backend: str = "jnp", interpret: bool = True):
    """frames (k, h, w) float32 -> (symbols (k, hb, wb, 8, 8) int16)."""

    def step(pred, frame):
        resid = (frame - pred)[None]
        if backend == "pallas":
            sym = _dct8.dct8_quantize(resid, quant_scale,
                                      interpret=interpret)[0]
            recon_resid = _dct8.dct8_dequantize(sym[None], quant_scale,
                                                interpret=interpret)[0]
        else:
            sym = T.frames_to_symbols(resid, quant_scale)[0]
            recon_resid = T.symbols_to_residuals(sym[None], quant_scale)[0]
        recon = jnp.clip(pred + recon_resid, 0.0, 255.0)
        return recon, sym

    init = jnp.full(frames_f32.shape[1:], 128.0, frames_f32.dtype)
    _, symbols = jax.lax.scan(step, init, frames_f32)
    return symbols


@functools.partial(jax.jit, static_argnames=())
def _decode_chunk(symbols: jnp.ndarray, quant_scale: jnp.ndarray):
    """Per-chunk reference decoder (k, hb, wb, 8, 8) int16 -> (k, h, w) f32.

    The seed decode path: dequantize+IDCT trapped inside the DPCM scan, one
    dispatch per chunk.  Kept as the bit-exactness oracle for the batched
    ``_decode_chunks`` and as the baseline of the ``decode_path`` bench."""

    def step(pred, sym):
        recon_resid = T.symbols_to_residuals(sym[None], quant_scale)[0]
        recon = jnp.clip(pred + recon_resid, 0.0, 255.0)
        return recon, recon

    k, hb, wb, _, _ = symbols.shape
    init = jnp.full((hb * T.BLOCK, wb * T.BLOCK), 128.0, jnp.float32)
    _, frames = jax.lax.scan(step, init, symbols)
    return frames


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def _chunk_residuals(symbols: jnp.ndarray, quant_scale: jnp.ndarray,
                     backend: str = "jnp", interpret: bool = True):
    """One-dispatch batched residual IDCT: (C, k, hb, wb, 8, 8) int16 ->
    (k, C, h, w) float32 residuals for ALL wanted chunks' frames at once
    (one fused Pallas dispatch or one fused jnp program), hoisted out of
    the DPCM recursion.  k-major layout so the downstream scan consumes
    its leading axis with no float32 transposes."""
    C, k, hb, wb, _, _ = symbols.shape
    kmajor = symbols.transpose(1, 0, 2, 3, 4, 5).reshape(
        k * C, hb, wb, T.BLOCK, T.BLOCK)
    if backend == "pallas":
        resid = _dct8.dct8_dequantize(kmajor, quant_scale,
                                      interpret=interpret)
    else:
        resid = T.symbols_to_residuals(kmajor, quant_scale)
    return resid.reshape(k, C, hb * T.BLOCK, wb * T.BLOCK)


@jax.jit
def _residuals_scan(resid: jnp.ndarray) -> jnp.ndarray:
    """The cheap sequential DPCM tail over precomputed residuals:
    (k, C, h, w) f32 -> (k, C, h, w) u8.  Each step adds+clips and emits
    rounded uint8 directly, so the float32 frame stack never materializes
    in memory (only the (C, h, w) carry stays float)."""

    def step(pred, r):
        recon = jnp.clip(pred + r, 0.0, 255.0)
        return recon, jnp.round(recon).astype(jnp.uint8)

    init = jnp.full(resid.shape[1:], 128.0, jnp.float32)
    _, frames = jax.lax.scan(step, init, resid)
    return frames


def _decode_chunks(symbols: jnp.ndarray, quant_scale: jnp.ndarray,
                   backend: str = "jnp", interpret: bool = True):
    """Batched chunk decode: (C, k, hb, wb, 8, 8) int16 -> (k, C, h, w) u8.

    Two jit dispatches regardless of chunk count — the batched residual
    IDCT and the add+clip scan (kept as separate programs: XLA:CPU fuses
    the transform into the scan body when compiled together, which is
    measurably slower).  Per-frame float ops and their order match
    ``_decode_chunk`` exactly, so reconstruction is bit-exact with the
    per-chunk path; callers index ``[frame_in_chunk, chunk_row]``."""
    return _residuals_scan(_chunk_residuals(symbols, quant_scale,
                                            backend=backend,
                                            interpret=interpret))


def _pad_chunk_count(c: int) -> int:
    """Next power of two >= c: the static chunk-batch shapes ``_decode_chunks``
    compiles for, so arbitrary want-sets reuse a small ladder of jit entries."""
    return 1 << max(0, c - 1).bit_length()


def _k_eff(k: int, n: int) -> int:
    """The chunk-stack frame dimension: ``min(k, n)``.  A keyframe interval
    larger than the segment yields a single chunk of n frames — padding to
    the full interval would scan k-n ghost frames per chunk."""
    return min(k, n)


def _pad_tail(chunk: np.ndarray, k_eff: int) -> np.ndarray:
    """Edge-pad a short tail chunk to the (effective) keyframe interval
    (DPCM is causal, so padded frames cannot affect the real frames'
    symbols)."""
    if len(chunk) == k_eff:
        return chunk
    return np.concatenate(
        [chunk, np.repeat(chunk[-1:], k_eff - len(chunk), axis=0)])


# ---------------------------------------------------------------------------
# Public segment API
# ---------------------------------------------------------------------------

def encode_segment(frames_u8: np.ndarray, *, quant_scale: float,
                   keyframe_interval: int, zstd_level: int,
                   version: int | None = None) -> bytes:
    """Encode (n, h, w) uint8 frames.  n need not divide the interval; the
    final chunk is simply shorter (padded for the jit call, sliced before
    serialization).  ``version`` selects the blob format (default
    ``DEFAULT_VERSION``); v1 is retained for back-compat tests/benches."""
    version = DEFAULT_VERSION if version is None else version
    if version not in (1, 2):
        raise ValueError(f"unknown blob format version {version}")
    frames = np.asarray(frames_u8)
    n, h, w = frames.shape
    k = keyframe_interval
    backend, interp = T.dct_backend(), T.dct_interpret()
    parts = []
    with _span("codec.transform", chunks=-(-n // k), frames=n):
        for start in range(0, n, k):
            kc = min(k, n - start)
            chunk = T.to_device(_pad_tail(frames[start:start + kc],
                                          _k_eff(k, n)), jnp.float32)
            sym = _encode_chunk(chunk, jnp.float32(quant_scale),
                                backend=backend, interpret=interp)
            parts.append(T.to_host(sym)[:kc])
    header = {
        "magic": _MAGIC, "raw": False, "n": n, "h": h, "w": w,
        "k": k, "qs": float(quant_scale), "lvl": zstd_level,
    }
    with _span("codec.compress", chunks=len(parts)) as csp:
        if version == 1:
            coder, comp = _compress(b"".join(p.tobytes() for p in parts),
                                    zstd_level)
            header["ec"] = coder
            payload = comp
        else:
            spans, blobs = [], []
            coder = None
            for p in parts:
                coder, comp = _compress(p.tobytes(), zstd_level)
                spans.append(len(comp))
                blobs.append(comp)
            header["v"] = 2
            header["ec"] = coder or _compress(b"", zstd_level)[0]
            header["spans"] = spans
            payload = b"".join(blobs)
        csp.set(bytes=len(payload))
    packed = msgpack.packb(header)
    return struct.pack("<I", len(packed)) + packed + payload


def encode_raw(frames_u8: np.ndarray) -> bytes:
    """Coding bypass: store raw frames (true random access, no decode)."""
    frames = np.ascontiguousarray(np.asarray(frames_u8, np.uint8))
    n, h, w = frames.shape
    header = msgpack.packb({"magic": _MAGIC, "raw": True, "n": n, "h": h, "w": w})
    return struct.pack("<I", len(header)) + header + frames.tobytes()


def _parse(blob: bytes):
    (hlen,) = struct.unpack_from("<I", blob, 0)
    header = msgpack.unpackb(blob[4:4 + hlen])
    if header.get("magic") != _MAGIC:
        raise ValueError("not a tpucodec blob")
    return header, blob[4 + hlen:]


def segment_info(blob: bytes) -> dict:
    header, _ = _parse(blob)
    return header


def _zstd_frame_size(span) -> int:
    """Bytes of the zstd frame that starts ``span``, read from its frame
    and block headers (RFC 8878 §3.1): a stream decoder stops quietly at
    the end of its input, so this is how a truncated frame is told from a
    whole one."""
    pos = zstandard.frame_header_size(span)
    while True:
        if pos + 3 > len(span):
            raise ValueError("truncated zstd frame")
        block = int.from_bytes(span[pos:pos + 3], "little")
        rle = (block >> 1) & 3 == 1  # an RLE block stores one byte
        pos += 3 + (1 if rle else block >> 3)
        if block & 1:  # the frame's last block
            break
    if zstandard.get_frame_parameters(span).has_checksum:
        pos += 4
    return pos


def _zstd_into(span, dst: np.ndarray) -> None:
    """Decompress the one zstd frame in ``span`` straight into ``dst``'s
    bytes.  The frame must end where ``span`` ends and fill ``dst``
    exactly; a short, long or corrupt span raises, as ``decompress`` and a
    ``reshape`` to ``dst``'s shape would."""
    if _zstd_frame_size(span) != len(span):
        raise ValueError("zstd chunk span is not one whole frame")
    view = memoryview(dst).cast("B")
    with zstandard.ZstdDecompressor().stream_reader(
            span, read_across_frames=False) as reader:
        got = 0
        while got < len(view):
            n = reader.readinto(view[got:])
            if n == 0:
                break
            got += n
        if got != len(view) or reader.read(1):
            raise ValueError(f"zstd chunk does not hold {len(view)} bytes")


def _symbol_stack(header: dict, rows: int) -> np.ndarray:
    """A fresh, uninitialised (rows, k_eff, hb, wb, 8, 8) int16 stack for
    :func:`_chunk_symbols` to fill.  Never reused: the device upload of the
    last one (on the CPU backend, an array aliasing it) may still read it."""
    return np.empty((rows, _k_eff(header["k"], header["n"]),
                     header["h"] // T.BLOCK, header["w"] // T.BLOCK,
                     T.BLOCK, T.BLOCK), np.int16)


def _chunk_symbols(header: dict, payload: bytes, chunks: np.ndarray,
                   out: np.ndarray) -> tuple[int, int, int]:
    """Entropy-decode the selected ``chunks`` into rows ``0..len(chunks)``
    of the (rows, k_eff, hb, wb, 8, 8) int16 stack ``out``, and zero every
    byte of ``out`` no chunk writes: a short tail chunk's missing frames
    and the rows after the chunks (DPCM is causal, so the decoder sees the
    zeros a zero-filled stack would give).  ``out``'s prior contents are
    never read.

    v2 zstd chunks decompress in place; zlib chunks and v1 blobs (one
    stream for the whole segment) have no in-place decoder and are
    decompressed, then copied.  Returns (payload_bytes_touched,
    direct_chunks, staged_bytes): v2 touches only the selected chunks'
    spans, v1 the whole stream; ``staged_bytes`` counts what was zeroed or
    copied into ``out`` beyond what a decompressor wrote there."""
    n, k = header["n"], header["k"]
    ec = header.get("ec", "zstd")
    staged = out[len(chunks):].nbytes
    out[len(chunks):] = 0
    if header.get("v", 1) >= 2:
        offsets = np.concatenate([[0], np.cumsum(header["spans"])])
        buf = memoryview(payload)
        touched = direct = 0
        for i, c in enumerate(chunks):
            c = int(c)
            kc = min(k, n - c * k)
            span = buf[offsets[c]:offsets[c + 1]]
            if ec == "zstd" and zstandard is not None:
                _zstd_into(span, out[i, :kc])
                direct += 1
            else:
                out[i, :kc] = np.frombuffer(
                    _decompress(ec, span), np.int16).reshape(
                    kc, *out.shape[2:])
                staged += out[i, :kc].nbytes
            staged += out[i, kc:].nbytes
            out[i, kc:] = 0
            touched += int(header["spans"][c])
        return touched, direct, staged
    sym_all = np.frombuffer(_decompress(ec, payload), np.int16).reshape(
        n, *out.shape[2:])
    for i, c in enumerate(chunks):
        start = int(c) * k
        kc = min(k, n - start)
        out[i, :kc] = sym_all[start:start + kc]
        out[i, kc:] = 0
        staged += out[i].nbytes
    return len(payload), 0, staged


def _decode_cost(header: dict, header_bytes: int, payload_bytes: int,
                 chunks: int, frames: int) -> dict:
    """The header dict augmented with bytes/chunks/frames actually touched —
    what ``VideoStore.decode_for`` reports, from the single parse that the
    decode itself performed."""
    return dict(header) | {
        "bytes": header_bytes + payload_bytes,
        "chunks": chunks,
        "frames": frames,
    }


def decode_segment_ex(blob: bytes,
                      want: np.ndarray | None = None
                      ) -> tuple[np.ndarray, dict]:
    """Decode stored frames and return ``(frames, info)`` from one parse.

    ``want`` (sorted indices into the stored frame sequence) enables
    chunk-skip: only chunks containing wanted frames are entropy-decoded
    (v2: only their payload bytes are even touched) and reconstructed, all
    in a single batched jit dispatch.  ``info`` is the blob header plus
    ``bytes``/``chunks``/``frames`` actually touched, so callers need no
    second ``segment_info`` parse."""
    with _span("codec.parse", bytes=len(blob)):
        header, payload = _parse(blob)
    hlen = len(blob) - len(payload)
    n, h, w = header["n"], header["h"], header["w"]
    if header["raw"]:
        return _decode_raw(header, payload, hlen, want)

    k = header["k"]
    want = np.arange(n) if want is None else np.asarray(want, np.int64)
    if want.size == 0:
        return (np.empty((0, h, w), np.uint8),
                _decode_cost(header, hlen, 0, 0, 0))
    chunk_of = want // k
    chunks = np.unique(chunk_of)
    with _span("codec.entropy", chunks=len(chunks)) as esp:
        sym = _symbol_stack(header, _pad_chunk_count(len(chunks)))
        touched, direct, staged = _chunk_symbols(header, payload, chunks,
                                                 sym)
        esp.set(bytes=touched, direct_chunks=direct, staged_bytes=staged)
    with _span("codec.residuals", chunks=len(chunks), frames=len(want)):
        decoded = _run_decode(sym, header)  # (k_eff, C_padded, h, w)
    out = _scatter_rows(decoded, want, k, chunks)
    return out, _decode_cost(header, hlen, touched, len(chunks), len(want))


def _decode_raw(header: dict, payload: bytes, hlen: int,
                want: np.ndarray | None) -> tuple[np.ndarray, dict]:
    """Coding-bypass read: slice (or, for a dense read, copy — frombuffer
    views are read-only and callers may mutate) the raw frame array."""
    n, h, w = header["n"], header["h"], header["w"]
    frames = np.frombuffer(payload, np.uint8).reshape(n, h, w)
    out = frames[want] if want is not None else frames.copy()
    return out, _decode_cost(header, hlen, out.nbytes, 0, len(out))


def _run_decode(sym_padded: np.ndarray, header: dict) -> np.ndarray:
    """One ``_decode_chunks`` dispatch on the resolved transform backend."""
    return T.to_host(_decode_chunks(
        T.to_device(sym_padded), jnp.float32(header["qs"]),
        backend=T.dct_backend(), interpret=T.dct_interpret()))


def _scatter_rows(decoded: np.ndarray, want: np.ndarray, k: int,
                  chunks: np.ndarray, row0: int = 0) -> np.ndarray:
    """Select ``want`` frames from a decoded (k_eff, C, h, w) chunk stack
    whose rows ``row0 .. row0+len(chunks)`` hold ``chunks`` (sorted unique).
    The single scatter-math implementation shared by the one-segment and
    grouped decoders, so their indexing cannot diverge."""
    chunk_of = want // k
    rows = row0 + np.searchsorted(chunks, chunk_of)
    return decoded[want - chunk_of * k, rows]


def decode_segment(blob: bytes, want: np.ndarray | None = None) -> np.ndarray:
    """Decode stored frames (see ``decode_segment_ex``; this drops the cost
    info).  Returns (len(want) or n, h, w) uint8, always writable."""
    return decode_segment_ex(blob, want)[0]


def decode_many(blobs: list[bytes],
                want: np.ndarray | None = None
                ) -> tuple[list[np.ndarray], dict]:
    """Decode several segments' ``want`` frames with ONE batched dispatch.

    All coded blobs sharing a transform shape (h, w, k, qs) — which every
    segment of one storage format does — contribute their wanted chunks to
    a single stacked ``_decode_chunks`` call; raw or odd-shaped blobs fall
    back to per-blob decode.  Returns ``(frames_per_blob, cost)`` where
    cost aggregates bytes/chunks/frames touched plus the jit ``dispatches``
    issued (one per distinct coded shape group; raw blobs need none)."""
    outs: list[np.ndarray | None] = [None] * len(blobs)
    cost = {"bytes": 0, "chunks": 0, "frames": 0, "dispatches": 0}
    groups: dict[tuple, list] = {}
    for i, blob in enumerate(blobs):
        header, payload = _parse(blob)
        hlen = len(blob) - len(payload)
        if header["raw"]:
            outs[i], info = _decode_raw(header, payload, hlen, want)
            for key in ("bytes", "chunks", "frames"):
                cost[key] += info[key]
            continue
        key = (header["h"], header["w"], header["k"], header["qs"],
               _k_eff(header["k"], header["n"]))
        groups.setdefault(key, []).append((i, header, payload, hlen))

    for (_h, _w, k, _qs, _ke), members in groups.items():
        per_member = []
        total_chunks = 0
        for i, header, payload, hlen in members:
            n = header["n"]
            w_i = (np.arange(n) if want is None
                   else np.asarray(want, np.int64))
            chunks = np.unique(w_i // k) if w_i.size else np.empty(0, np.int64)
            per_member.append((i, header, payload, hlen, w_i, chunks))
            total_chunks += len(chunks)
        if total_chunks == 0:
            for i, header, payload, hlen, w_i, _c in per_member:
                outs[i] = np.empty((0, header["h"], header["w"]), np.uint8)
                cost["bytes"] += hlen
            continue
        header0 = per_member[0][1]
        sym = _symbol_stack(header0, _pad_chunk_count(total_chunks))
        row = direct = staged = 0
        rowspans = []
        with _span("codec.entropy", chunks=total_chunks,
                   segments=len(per_member)) as esp:
            for i, header, payload, hlen, w_i, chunks in per_member:
                touched, d, s = _chunk_symbols(
                    header, payload, chunks, sym[row:row + len(chunks)])
                rowspans.append(row)
                row += len(chunks)
                direct += d
                staged += s
                cost["bytes"] += hlen + touched
                cost["chunks"] += len(chunks)
                cost["frames"] += len(w_i)
            staged += sym[row:].nbytes
            sym[row:] = 0
            esp.set(bytes=cost["bytes"], direct_chunks=direct,
                    staged_bytes=staged)
        with _span("codec.residuals", chunks=total_chunks,
                   frames=cost["frames"]):
            decoded = _run_decode(sym, header0)
        cost["dispatches"] += 1
        for (i, header, payload, hlen, w_i, chunks), r0 in zip(per_member,
                                                              rowspans):
            if w_i.size == 0:
                outs[i] = np.empty((0, header["h"], header["w"]), np.uint8)
                continue
            outs[i] = _scatter_rows(decoded, w_i, k, chunks, row0=r0)
    return outs, cost


def decode_segment_scan(blob: bytes,
                        want: np.ndarray | None = None) -> np.ndarray:
    """The seed decode path, kept as oracle and bench baseline: one
    ``_decode_chunk`` jit dispatch + one float32 host transfer per wanted
    chunk, with the dequantize+IDCT inside the DPCM scan, and (for v1
    blobs) a whole-payload entropy decode."""
    header, payload = _parse(blob)
    n, h, w = header["n"], header["h"], header["w"]
    if header["raw"]:
        frames = np.frombuffer(payload, np.uint8).reshape(n, h, w)
        return frames[want] if want is not None else frames.copy()
    k, qs = header["k"], np.float32(header["qs"])
    want = np.arange(n) if want is None else np.asarray(want, np.int64)
    out = np.empty((len(want), h, w), np.uint8)
    chunk_of = want // k
    chunks = np.unique(chunk_of)
    sym_all = _symbol_stack(header, len(chunks))
    _chunk_symbols(header, payload, chunks, sym_all)
    for row, c in enumerate(chunks):
        kc = min(k, n - int(c) * k)
        # analysis: allow[jit-shape] per-chunk reference oracle, not a
        # serving path: decode_segment_scan exists to cross-check the
        # batched decoder bit-for-bit, and the tail chunk's kc<k shape
        # is the exact semantics it must replicate
        frames = np.asarray(_decode_chunk(jnp.asarray(sym_all[row, :kc]), qs))
        sel = np.nonzero(chunk_of == c)[0]
        out[sel] = np.clip(np.round(frames[want[sel] - int(c) * k]),
                           0, 255).astype(np.uint8)
    return out


def decoded_chunks(n: int, k: int, want: np.ndarray) -> int:
    """How many chunks a decode of ``want`` touches (cost accounting)."""
    return len(np.unique(np.asarray(want) // k))
