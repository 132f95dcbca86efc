"""Fused batched decode path: bit-exact equivalence against the seed
per-chunk scan decoder (property over want sets, keyframe intervals, blob
versions, and entropy coders), v1 back-compat, chunk-granular byte
accounting, batched multi-segment decode, entropy decode straight into the
symbol stack (against a zero-fill-and-copy reference, corrupt spans, span
counters), Pallas-vs-jnp oracle checks, and jit-cache stability for tail
chunks."""

import struct
import zlib

import msgpack
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.codec import segment as S
from repro.codec import transform as T
from repro.codec.transform import temporal_indices
from repro.core.knobs import FidelityOption, IngestSpec
from repro.obs import trace as obstrace

try:
    import zstandard
except ImportError:  # the codec falls back to zlib without it
    zstandard = None

needs_zstd = pytest.mark.skipif(zstandard is None,
                                reason="in-place decode is zstd's")


def _frames(n=16, h=48, w=64, seed=0):
    rng = np.random.default_rng(seed)
    t = np.arange(n)[:, None, None]
    y = np.arange(h)[None, :, None]
    x = np.arange(w)[None, None, :]
    f = 120 + 50 * np.sin((x + 2 * t) / 9) + 30 * np.cos((y - t) / 7)
    return (f + rng.normal(0, 3, (n, h, w))).clip(0, 255).astype(np.uint8)


def _encode(f, *, kint=5, version=None, qs=2.0, lvl=3):
    return S.encode_segment(f, quant_scale=qs, keyframe_interval=kint,
                            zstd_level=lvl, version=version)


# ---------------------------------------------------------------------------
# bit-exact equivalence with the seed scan decoder
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000), st.sampled_from([3, 5, 10, 50]),
       st.integers(0, 16), st.sampled_from([1, 2]))
def test_batched_decode_matches_seed_scan(seed, kint, n_want, version):
    f = _frames(n=13, seed=seed)  # 13 !% kint exercises the tail chunk
    blob = _encode(f, kint=kint, version=version)
    rng = np.random.default_rng(seed)
    want = np.sort(rng.choice(len(f), size=min(n_want, len(f)),
                              replace=False))
    assert np.array_equal(S.decode_segment(blob, want),
                          S.decode_segment_scan(blob, want))
    assert np.array_equal(S.decode_segment(blob),
                          S.decode_segment_scan(blob))


@pytest.mark.parametrize("version", [1, 2])
def test_zlib_coder_roundtrip(version, monkeypatch):
    """Both blob versions stay self-describing under the zlib fallback."""
    monkeypatch.setattr(S, "zstandard", None)
    f = _frames()
    blob = _encode(f, version=version)
    assert S.segment_info(blob)["ec"] == "zlib"
    assert np.array_equal(S.decode_segment(blob),
                          S.decode_segment_scan(blob))


def test_repeated_and_empty_want():
    f = _frames()
    blob = _encode(f)
    full = S.decode_segment(blob)
    want = np.array([2, 2, 7, 7, 7, 12])  # temporal_indices can repeat
    assert np.array_equal(S.decode_segment(blob, want), full[want])
    out, info = S.decode_segment_ex(blob, np.empty(0, np.int64))
    assert out.shape == (0, 48, 64) and info["chunks"] == 0


# ---------------------------------------------------------------------------
# v1 back-compat + byte accounting
# ---------------------------------------------------------------------------

def test_v1_blob_backcompat():
    f = _frames()
    blob = _encode(f, version=1)
    info = S.segment_info(blob)
    assert "v" not in info and "spans" not in info
    full, cost = S.decode_segment_ex(blob)
    assert np.array_equal(full, S.decode_segment_scan(blob))
    # v1 must decompress the whole payload whatever the want set
    _, sparse_cost = S.decode_segment_ex(blob, np.array([0]))
    assert sparse_cost["bytes"] == cost["bytes"] == len(blob)


def test_v2_sparse_read_touches_fewer_bytes():
    f = _frames(n=32)
    blob = _encode(f, kint=5, version=2)
    info = S.segment_info(blob)
    header_bytes = len(blob) - sum(info["spans"])
    full, cost_full = S.decode_segment_ex(blob)
    assert cost_full["bytes"] == len(blob)  # dense touches everything
    part, cost = S.decode_segment_ex(blob, np.array([7]))
    assert np.array_equal(part, full[[7]])
    assert cost["chunks"] == 1
    assert cost["bytes"] == header_bytes + info["spans"][1]
    assert cost["bytes"] < len(blob) // 2


def test_decode_for_cost_from_single_parse(tmp_path):
    """VideoStore.decode_for reports touched bytes/chunks without a second
    segment_info parse, and sparse v2 reads are charged per chunk."""
    from repro.core.knobs import CodingOption, StorageFormat
    from repro.videostore import VideoStore

    spec = IngestSpec()
    vs = VideoStore(str(tmp_path), spec)
    sf = StorageFormat(FidelityOption(), CodingOption("fast", 5))
    vs.set_formats({"sf0": sf})
    f = _frames(spec.frames_per_segment, spec.height, spec.width)
    vs.ingest_segment("s", 0, f)
    blob_len = vs.backend.get("s:sf0:000000")
    dense, dcost = vs.decode_for("s", 0, "sf0", np.arange(len(f)))
    sparse, scost = vs.decode_for("s", 0, "sf0", np.array([3]))
    assert np.array_equal(sparse[0], dense[3])
    assert scost["chunks"] == 1 and dcost["chunks"] == -(-len(f) // 5)
    assert scost["bytes"] < dcost["bytes"] == len(blob_len)


# ---------------------------------------------------------------------------
# batched multi-segment decode
# ---------------------------------------------------------------------------

def test_decode_many_matches_per_blob():
    blobs = [_encode(_frames(seed=s), kint=5) for s in range(4)]
    want = np.array([0, 6, 11])
    outs, cost = S.decode_many(blobs, want)
    for blob, out in zip(blobs, outs):
        assert np.array_equal(out, S.decode_segment(blob, want))
    assert cost["dispatches"] == 1  # one fused jit call for all four
    assert cost["chunks"] == 4 * 3 and cost["frames"] == 4 * 3


def test_decode_many_dense_and_mixed_raw():
    coded = _encode(_frames(seed=1), kint=10)
    raw = S.encode_raw(_frames(seed=2))
    outs, cost = S.decode_many([coded, raw, coded], None)
    assert np.array_equal(outs[0], S.decode_segment(coded))
    assert np.array_equal(outs[1], S.decode_segment(raw))
    assert np.array_equal(outs[2], outs[0])
    assert cost["dispatches"] == 1  # raw needs no jit dispatch at all
    outs[1][0, 0, 0] ^= 0xFF  # raw fallback must also be writable


def test_retrieve_many_uses_batched_decode(tmp_path):
    from repro.core.knobs import CodingOption, StorageFormat
    from repro.videostore import VideoStore

    spec = IngestSpec()
    vs = VideoStore(str(tmp_path), spec)
    sf = StorageFormat(FidelityOption(), CodingOption("fast", 10))
    vs.set_formats({"sf0": sf})
    for seg in range(3):
        vs.ingest_segment("s", seg, _frames(spec.frames_per_segment,
                                            spec.height, spec.width,
                                            seed=seg))
    cf = FidelityOption("good", 1.0, 360, 1 / 2)
    many, cost = vs.retrieve_many("s", [0, 1, 2], "sf0", cf)
    for seg, out in enumerate(many):
        one, _ = vs.retrieve("s", seg, "sf0", cf)
        assert np.array_equal(out, one)
    assert cost["chunks"] > 0 and cost["bytes"] > 0


# ---------------------------------------------------------------------------
# entropy decode straight into the symbol stack
# ---------------------------------------------------------------------------

_GARBAGE = 0x5A5A  # stands in for what an uninitialised stack may hold
_FRAME_BYTES = (48 // 8) * (64 // 8) * 64 * 2  # int16 symbols of a frame


def _inflate(coder, span):
    if coder == "zlib":
        return zlib.decompress(span)
    return zstandard.ZstdDecompressor().decompress(span)


def _staged_symbols(blob, chunks, pad_to):
    """Test-local reference: a zero-filled stack, each chunk (v2) or the
    whole stream (v1) decompressed into a buffer of its own, then copied."""
    header, payload = S._parse(blob)
    n, k = header["n"], header["k"]
    frame = (header["h"] // 8, header["w"] // 8, 8, 8)
    out = np.zeros((pad_to, min(k, n)) + frame, np.int16)
    if header.get("v", 1) == 1:
        sym = np.frombuffer(_inflate(header["ec"], payload),
                            np.int16).reshape((n,) + frame)
        for i, c in enumerate(chunks):
            out[i, :min(k, n - c * k)] = sym[c * k:(c + 1) * k]
        return out
    offs = np.cumsum([0] + list(header["spans"]))
    for i, c in enumerate(chunks):
        raw = _inflate(header["ec"], payload[offs[c]:offs[c + 1]])
        out[i, :min(k, n - c * k)] = np.frombuffer(raw, np.int16).reshape(
            (-1,) + frame)
    return out


def _encode_with(n, kint, version=None, coder="zstd", seed=0):
    f = _frames(n=n, seed=seed)
    if coder == "zlib":
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(S, "zstandard", None)
            return _encode(f, kint=kint, version=version)
    return _encode(f, kint=kint, version=version)


# (frames, keyframe interval, chunks, stack rows, blob version, coder)
_STACK_CASES = {
    "one_full_chunk_n_lt_k": (13, 50, [0], 1, 2, "zstd"),
    "chunks_with_short_tail": (13, 5, [0, 1, 2], 3, 2, "zstd"),
    "subset_of_chunks": (13, 3, [1, 4], 2, 2, "zstd"),
    "pad_rows_and_tail": (13, 5, [1, 2], 4, 2, "zstd"),
    "zlib": (13, 5, [0, 2], 4, 2, "zlib"),
    "v1": (13, 5, [0, 2], 4, 1, "zstd"),
}


@needs_zstd
@pytest.mark.parametrize("case", list(_STACK_CASES) + ["decode_many"])
def test_symbol_stack_matches_staged_reference(case, monkeypatch):
    """The stack the entropy decoder leaves is the zero-fill, decompress,
    copy stack byte for byte, whatever the stack held before: pad rows and
    a short tail's frames read zero, never leftover memory."""
    if case == "decode_many":
        blobs = [_encode_with(n, 5, seed=n) for n in (13, 10, 7)]
        monkeypatch.setattr(
            S, "_symbol_stack", lambda header, rows: np.full(
                (rows, 5, 6, 8, 8, 8), _GARBAGE, np.int16))
        seen = []
        run = S._run_decode
        monkeypatch.setattr(S, "_run_decode",
                            lambda sym, header: seen.append(sym.copy())
                            or run(sym, header))
        outs, _ = S.decode_many(blobs)
        chunks = [np.arange(-(-n // 5)) for n in (13, 10, 7)]
        want = np.concatenate(
            [_staged_symbols(b, c, len(c)) for b, c in zip(blobs, chunks)]
            + [np.zeros((1, 5, 6, 8, 8, 8), np.int16)])   # 7 rows padded to 8
        assert len(seen) == 1 and seen[0].tobytes() == want.tobytes()
        for blob, out in zip(blobs, outs):
            assert np.array_equal(out, S.decode_segment(blob))
        return
    n, kint, chunks, rows, version, coder = _STACK_CASES[case]
    blob = _encode_with(n, kint, version, coder)
    header, payload = S._parse(blob)
    out = np.full((rows, min(kint, n), 6, 8, 8, 8), _GARBAGE, np.int16)
    S._chunk_symbols(header, payload, np.array(chunks), out)
    assert out.tobytes() == _staged_symbols(blob, chunks, rows).tobytes()


def _with_chunk_span(blob, c, edit):
    """``blob`` with chunk ``c``'s compressed span replaced by
    ``edit(span)`` and the header's spans updated to match."""
    header, payload = S._parse(blob)
    offs = np.cumsum([0] + list(header["spans"]))
    spans = [payload[offs[i]:offs[i + 1]] for i in range(len(offs) - 1)]
    spans[c] = edit(spans[c])
    packed = msgpack.packb(dict(header, spans=[len(s) for s in spans]))
    return struct.pack("<I", len(packed)) + packed + b"".join(spans)


def _deflate(raw, **kw):
    return zstandard.ZstdCompressor(level=3, **kw).compress(raw)


def _flip(span, at):
    b = bytearray(span)
    b[at] ^= 0x80
    return bytes(b)


_CORRUPT = {
    "truncated_by_one": lambda s: s[:-1],
    "truncated_to_half": lambda s: s[:len(s) // 2],
    "byte_flipped_in_data": lambda s: _flip(s, len(s) // 2),
    "byte_flipped_in_block_header": lambda s: _flip(
        s, zstandard.frame_header_size(s)),
    "frame_appended": lambda s: s + s,
    "one_symbol_more": lambda s: _deflate(_inflate("zstd", s) + b"\0\0"),
    "one_symbol_fewer": lambda s: _deflate(_inflate("zstd", s)[:-2]),
    # a stream decoder stops quietly inside a frame's checksum
    "cut_in_checksum": lambda s: _deflate(_inflate("zstd", s),
                                          write_checksum=True)[:-1],
}


@needs_zstd
@pytest.mark.parametrize("corrupt", list(_CORRUPT))
def test_corrupt_zstd_chunk_raises(corrupt):
    """A chunk span that is short, long or corrupt raises and returns no
    stack.  (A whole-buffer ``decompress`` ignores a second frame after
    the first; the in-place decoder refuses a span that is not one
    frame.)"""
    blob = _with_chunk_span(_encode(_frames(), kint=5), 1, _CORRUPT[corrupt])
    header, payload = S._parse(blob)
    with pytest.raises((ValueError, zstandard.ZstdError)):
        S.decode_segment(blob, np.array([6]))
    out = np.empty((1, 5, 6, 8, 8, 8), np.int16)
    with pytest.raises((ValueError, zstandard.ZstdError)):
        S._chunk_symbols(header, payload, np.array([1]), out)


@needs_zstd
def test_every_bit_flip_decodes_as_decompress_does():
    """Over every single-bit flip of one chunk's span, decompressing in
    place raises exactly where ``decompress`` and a reshape raise, and
    otherwise gives the same symbols."""
    header, payload = S._parse(_encode(_frames(), kint=5))
    offs = np.cumsum([0] + list(header["spans"]))
    span = payload[offs[1]:offs[2]]
    for at in range(len(span)):
        for bit in (0x01, 0x80):
            bad = bytearray(span)
            bad[at] ^= bit
            got = want = None
            try:
                want = np.frombuffer(zstandard.ZstdDecompressor().decompress(
                    bytes(bad)), np.int16).reshape(5, 6, 8, 8, 8)
            except (ValueError, MemoryError, zstandard.ZstdError):
                pass  # a flipped content size can ask for exabytes
            try:
                got = np.empty((5, 6, 8, 8, 8), np.int16)
                S._zstd_into(bytes(bad), got)
            except (ValueError, zstandard.ZstdError):
                got = None
            assert (got is None) == (want is None), (at, bit)
            assert got is None or np.array_equal(got, want), (at, bit)


@pytest.fixture
def traced():
    """The process tracer, enabled and empty; restored afterwards."""
    obstrace.TRACER.drain()
    obstrace.enable(True)
    try:
        yield obstrace.TRACER
    finally:
        obstrace.enable(False)


# (blobs as (frames, keyframe interval, version, coder), direct chunks,
# staged bytes): staged is what was zeroed or copied into the stack beyond
# what zstd wrote straight into it
_COUNTER_CASES = {
    "one_full_chunk": ([(13, 50, 2, "zstd")], 1, 0),
    # 3 chunks padded to 4 rows: the pad row and the tail's 2 frames
    "pad_row_and_tail": ([(13, 5, 2, "zstd")], 3, (5 + 2) * _FRAME_BYTES),
    # zlib and v1 copy every frame: the whole 4-row stack is staged
    "zlib": ([(13, 5, 2, "zlib")], 0, 4 * 5 * _FRAME_BYTES),
    "v1": ([(13, 5, 1, "zstd")], 0, 4 * 5 * _FRAME_BYTES),
    # decode_many: 3 + 2 chunks padded to 8 rows, tails of 3 and 5 frames
    "decode_many": ([(13, 5, 2, "zstd"), (10, 5, 2, "zstd")], 5,
                    (3 * 5 + 2) * _FRAME_BYTES),
}


@needs_zstd
@pytest.mark.parametrize("case", _COUNTER_CASES)
def test_entropy_span_counts_direct_and_staged(case, traced):
    specs, direct, staged = _COUNTER_CASES[case]
    blobs = [_encode_with(*s) for s in specs]
    traced.drain()
    if len(blobs) == 1:
        S.decode_segment(blobs[0])
    else:
        S.decode_many(blobs)
    ent = [s for s in traced.drain() if s.name == "codec.entropy"]
    assert len(ent) == 1
    assert ent[0].attrs["direct_chunks"] == direct
    assert ent[0].attrs["staged_bytes"] == staged


def test_entropy_counters_record_nothing_untraced():
    obstrace.TRACER.drain()
    assert not obstrace.TRACER.enabled
    S.decode_segment(_encode(_frames(), kint=5))
    S.decode_many([_encode(_frames(), kint=5)] * 2)
    assert obstrace.TRACER.drain() == []


# ---------------------------------------------------------------------------
# raw-blob decode is writable
# ---------------------------------------------------------------------------

def test_raw_decode_returns_writable_copy():
    f = _frames()
    blob = S.encode_raw(f)
    out = S.decode_segment(blob)
    assert out.flags.writeable
    out += 1  # must not raise, must not corrupt the blob
    again = S.decode_segment(blob)
    assert np.array_equal(again, f)
    assert S.decode_segment_scan(blob).flags.writeable


# ---------------------------------------------------------------------------
# Pallas kernel wiring: oracle equivalence through the codec
# ---------------------------------------------------------------------------

@pytest.fixture
def _restore_backend():
    yield
    T.set_dct_backend("auto")


def test_pallas_backend_bit_identical(_restore_backend):
    f = _frames(n=6, h=16, w=24)
    T.set_dct_backend("jnp")
    blob_jnp = _encode(f, kint=3)
    dec_jnp = S.decode_segment(blob_jnp)
    T.set_dct_backend("pallas")  # interpret mode off-TPU
    blob_pl = _encode(f, kint=3)
    dec_pl = S.decode_segment(blob_jnp)
    assert blob_pl == blob_jnp          # encoder forward DCT matches
    assert np.array_equal(dec_pl, dec_jnp)  # fused residual IDCT matches


def test_ops_dispatch_follows_backend(_restore_backend):
    import jax.numpy as jnp

    from repro.kernels.dct8.ops import dct_dequantize, dct_quantize

    x = jnp.asarray(_frames(n=2, h=16, w=16), jnp.float32)
    for backend in ("jnp", "pallas"):
        T.set_dct_backend(backend)
        sym = dct_quantize(x, 2.0)
        rec = dct_dequantize(sym, 2.0)
        if backend == "jnp":
            base_sym, base_rec = np.asarray(sym), np.asarray(rec)
    np.testing.assert_array_equal(np.asarray(sym), base_sym)
    np.testing.assert_allclose(np.asarray(rec), base_rec, atol=1e-4)


def test_bad_backend_rejected():
    with pytest.raises(ValueError):
        T.set_dct_backend("cuda")


# ---------------------------------------------------------------------------
# jit-cache stability: tail chunks share the (k, h, w) compile
# ---------------------------------------------------------------------------

def test_tail_chunk_shares_jit_cache_entry():
    import jax

    if not hasattr(S._encode_chunk, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this jax")
    jax.clear_caches()
    f = _frames(n=13)  # 13 = 5 + 5 + 3: tail chunk shorter than k
    blob = _encode(f, kint=5)
    assert S._encode_chunk._cache_size() == 1
    S.decode_segment(blob)                    # 3 chunks -> padded to 4
    S.decode_segment(blob, np.array([1, 12]))  # 2 chunks -> padded to 2
    S.decode_segment(blob, np.array([0]))     # 1 chunk
    # one entry per padded chunk-count on the power-of-two ladder, never
    # one per raw tail shape
    assert S._chunk_residuals._cache_size() <= 3
    assert np.array_equal(S.decode_segment(blob),
                          S.decode_segment_scan(blob))


def test_pad_tail_does_not_change_real_symbols():
    """DPCM is causal: padding frames after the tail cannot change the
    stored symbols, so padded-encode == seed unpadded-encode."""
    f = _frames(n=13)
    import jax.numpy as jnp
    tail = f[10:13]
    sym_padded = np.asarray(S._encode_chunk(
        jnp.asarray(S._pad_tail(tail, 5), jnp.float32),
        jnp.float32(2.0)))[:3]
    sym_exact = np.asarray(S._encode_chunk(
        jnp.asarray(tail, jnp.float32), jnp.float32(2.0)))
    np.testing.assert_array_equal(sym_padded, sym_exact)


# ---------------------------------------------------------------------------
# end-to-end: query results identical on v1 and v2 blobs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("query", ["A", "B"])
def test_run_query_items_identical_v1_v2(query, tmp_path, monkeypatch):
    from repro.analytics.query import run_query
    from repro.analytics.scene import generate_segment
    from repro.launch.vserve import demo_config
    from repro.videostore import VideoStore

    spec = IngestSpec()
    cfg = demo_config()
    results = {}
    for version in (1, 2):
        monkeypatch.setattr(S, "DEFAULT_VERSION", version)
        vs = VideoStore(str(tmp_path / f"v{version}"), spec)
        vs.set_formats(cfg.storage_formats())
        for seg in range(3):
            frames, _ = generate_segment("jackson", seg, spec)
            vs.ingest_segment("jackson", seg, frames)
        results[version] = (
            run_query(vs, cfg, query, "jackson", [0, 1, 2], 0.8),
            run_query(vs, cfg, query, "jackson", [0, 1, 2], 0.8,
                      batch_segments=3))
    assert results[1][0].items == results[2][0].items
    assert results[1][1].items == results[2][0].items
    assert results[2][1].items == results[2][0].items


def test_sparse_sampling_decode_via_temporal_indices():
    """The chunk-skip driver (temporal_indices) composed with v2 spans: a
    1/30-sampled read of a 32-frame segment touches exactly one chunk."""
    spec = IngestSpec()
    f = _frames(spec.frames_per_segment, spec.height, spec.width)
    blob = _encode(f, kint=10, version=2)
    want = temporal_indices(FidelityOption(),
                            FidelityOption(sampling=1 / 30), spec)
    out, cost = S.decode_segment_ex(blob, want)
    assert np.array_equal(out, S.decode_segment_scan(blob, want))
    assert cost["chunks"] == len(np.unique(want // 10))
    assert cost["bytes"] < len(blob) // 2
