"""VideoStore: ingestion, multi-version storage, retrieval with chunk-skip
decode, and erosion execution — the data-path half of VStore (the
configuration engine in ``repro.core`` decides *what* formats this layer
materializes).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time

import numpy as np

from ..codec import segment as codec
from ..codec import transform as T
from ..obs.trace import span as _span
from ..core.knobs import (CodingOption, FidelityOption, IngestSpec,
                          StorageFormat)
from .store import SegmentStore


@dataclasses.dataclass
class IngestStats:
    """Per-ingest accounting: the paper's ingestion cost (transcode compute)
    and storage cost (bytes/sec of stored video).  Chunk-level byte spans
    (blob v2 headers) are recorded alongside — the chunk, not the segment,
    is the natural deletion quantum for erosion accounting."""
    encode_seconds: float = 0.0
    stored_bytes: int = 0
    segments: int = 0
    chunks: int = 0          # entropy-coded chunks written (0 for RAW blobs)
    chunk_bytes: int = 0     # payload bytes of those chunks (v2 spans)

    def add(self, sec: float, nbytes: int, chunks: int = 0,
            chunk_bytes: int = 0):
        self.encode_seconds += sec
        self.stored_bytes += nbytes
        self.chunks += chunks
        self.chunk_bytes += chunk_bytes

    def bytes_per_video_second(self, spec: IngestSpec) -> float:
        dur = max(1e-9, self.segments * spec.segment_seconds)
        return self.stored_bytes / dur

    def cost_xrealtime(self, spec: IngestSpec) -> float:
        """Transcode compute normalized to video realtime (1.0 = keeps up)."""
        dur = max(1e-9, self.segments * spec.segment_seconds)
        return self.encode_seconds / dur


@dataclasses.dataclass
class ErodeResult:
    """Byte-level accounting of one erosion sweep: what the executor needs
    to prove space was actually reclaimed.  ``chunks``/``chunk_bytes``
    break the reclaimed payload down to the chunk quantum (blob v2 spans);
    v1/RAW blobs report their whole payload under ``chunk_bytes`` with
    ``chunks`` = 0."""
    segments: int = 0
    bytes: int = 0
    chunks: int = 0
    chunk_bytes: int = 0
    victims: list[int] = dataclasses.field(default_factory=list)

    def merge(self, other: "ErodeResult") -> "ErodeResult":
        self.segments += other.segments
        self.bytes += other.bytes
        self.chunks += other.chunks
        self.chunk_bytes += other.chunk_bytes
        self.victims.extend(other.victims)
        return self


def _sf_key(sf_id: str, stream: str, seg: int) -> str:
    return f"{stream}:{sf_id}:{seg:06d}"


def stratified_pick(items: list, n_pick: int, seed: int = 0) -> list:
    """Pick ``n_pick`` of ``items`` spread evenly across the (ordered) list,
    deterministically: one pick per stratum of ``len/n_pick`` items, at a
    seed-derived phase within the stratum.  Unlike ``rng.choice`` this can
    never cluster all victims in one stretch of the timeline, so an eroded
    format degrades uniformly instead of losing a contiguous era."""
    n = len(items)
    if n_pick >= n:
        return list(items)
    if n_pick <= 0:
        return []
    # golden-ratio multiplicative hash: distinct seeds -> distinct phases
    phase = ((seed * 0x9E3779B9 + 0x7F4A7C15) % (1 << 32)) / float(1 << 32)
    stride = n / n_pick
    used: set[int] = set()
    out = []
    for i in range(n_pick):
        j = int((i + phase) * stride) % n
        while j in used:  # int() collisions: walk to the next free slot
            j = (j + 1) % n
        used.add(j)
        out.append(items[j])
    return sorted(out)


def blob_chunk_profile(blob: bytes) -> tuple[int, int]:
    """(chunks, chunk_bytes) of a stored blob: the number of entropy-coded
    chunks and their payload bytes.  v2 headers carry exact per-chunk byte
    spans; v1 charges the whole entropy stream and RAW blobs report their
    payload as chunkless bytes."""
    header = codec.segment_info(blob)
    if header.get("raw"):
        return 0, header["n"] * header["h"] * header["w"]
    spans = header.get("spans")
    if spans is not None:  # blob v2: exact per-chunk byte spans
        return len(spans), int(sum(spans))
    n, k = header["n"], header["k"]
    return -(-n // k), len(blob)


class VideoStore:
    """Owns the on-disk segments for all streams × storage formats.

    ``readonly=True`` attaches to an existing store without mutating it —
    no meta/identity writes, no compaction, writes raise — so another
    process (the cluster router) can inspect formats and the persisted
    ``store_id`` of a shard a worker process owns.  ``store_id`` is a
    random token minted when a writable store first touches its meta file
    and stable for the store's lifetime; the router's generation-checked
    reattach uses it to prove a restarted worker reopened the same data.
    """

    def __init__(self, root: str, spec: IngestSpec | None = None,
                 readonly: bool = False):
        self.root = root
        self.spec = spec or IngestSpec()
        self.readonly = readonly
        self.backend = SegmentStore(os.path.join(root, "segments"),
                                    readonly=readonly)
        self.formats: dict[str, StorageFormat] = {}
        self.store_id: str | None = None
        self.ingest_stats: dict[str, IngestStats] = {}  # guarded-by: _stats_mu
        self._meta_path = os.path.join(root, "meta.json")
        self._retriever = None  # serving-layer hook (see attach_retriever)
        self._fallback = None   # ingest-layer hook (see set_fallback)
        # the live path writes golden (ingest thread) and background
        # transcodes (worker thread) concurrently; stats stay consistent
        self._stats_mu = threading.Lock()
        self._load_meta()
        if self.store_id is None and not readonly:
            # analysis: allow[determinism] store identity is minted once
            # at creation and persisted in meta.json; it must be unique
            # across stores (shard-identity checks), not reproducible
            self.store_id = os.urandom(8).hex()
            self._save_meta()

    # -- configuration -------------------------------------------------------
    def set_formats(self, formats: dict[str, StorageFormat]):
        """Install the storage-format set derived by the config engine.
        Keys are stable sf ids ('sf_g', 'sf1', ...)."""
        if self.readonly:
            raise RuntimeError(f"read-only VideoStore at {self.root}")
        self.formats = dict(formats)
        self._save_meta()

    def _save_meta(self):
        blob = {
            sid: {
                "quality": sf.fidelity.quality, "crop": sf.fidelity.crop,
                "resolution": sf.fidelity.resolution,
                "sampling": sf.fidelity.sampling,
                "speed": sf.coding.speed, "keyframe": sf.coding.keyframe,
                "bypass": sf.coding.bypass,
            } for sid, sf in self.formats.items()
        }
        blob["__store__"] = {"store_id": self.store_id}
        tmp = self._meta_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=1)
        os.replace(tmp, self._meta_path)

    def _load_meta(self):
        if not os.path.exists(self._meta_path):
            return
        with open(self._meta_path) as f:
            blob = json.load(f)
        self.store_id = blob.pop("__store__", {}).get("store_id")
        self.formats = {
            sid: StorageFormat(
                FidelityOption(v["quality"], v["crop"], v["resolution"],
                               v["sampling"]),
                CodingOption(v["speed"], v["keyframe"], v["bypass"]))
            for sid, v in blob.items()
        }

    # -- ingestion ------------------------------------------------------------
    def encode_format(self, frames_u8: np.ndarray, src_f: FidelityOption,
                      sf: StorageFormat) -> bytes:
        """Transcode frames at fidelity ``src_f`` into ``sf``'s blob bytes
        (fidelity conversion + coding).  Deterministic: the single transcode
        implementation shared by blocking ingest, the background scheduler
        and fallback-chain reconstruction, so all three produce identical
        bytes from identical input."""
        with _span("encode.convert", fidelity=sf.fidelity.name(),
                   frames=len(frames_u8)):
            frames = T.to_host(T.convert_fidelity(frames_u8, src_f,
                                                  sf.fidelity, self.spec))
        if sf.coding.bypass:
            return codec.encode_raw(frames)
        return codec.encode_segment(
            frames, quant_scale=sf.fidelity.quant_scale,
            keyframe_interval=sf.coding.keyframe,
            zstd_level=sf.coding.zstd_level)

    def put_segment(self, stream: str, seg: int, sf_id: str, blob: bytes,
                    encode_s: float = 0.0, count_segment: bool = False):
        """Write one materialized blob and account it (bytes + chunk spans).
        ``count_segment`` increments the stream's segment counter — set by
        the path that writes the segment's first (golden) version."""
        chunks, chunk_bytes = blob_chunk_profile(blob)
        self.backend.put(_sf_key(sf_id, stream, seg), blob)
        with self._stats_mu:
            stats = self.ingest_stats.setdefault(stream, IngestStats())
            if count_segment:
                stats.segments += 1
            stats.add(encode_s, len(blob), chunks, chunk_bytes)

    def ingest_segment(self, stream: str, seg: int, frames_u8: np.ndarray,
                       ingest_fidelity: FidelityOption | None = None):
        """Blocking ingest: transcode one arriving segment into every
        configured storage format before returning.  ``frames_u8`` is at
        the ingest (richest) fidelity.  The live path (repro.ingest) writes
        only golden synchronously and materializes the rest in the
        background instead."""
        src_f = ingest_fidelity or FidelityOption()
        with self._stats_mu:
            stats = self.ingest_stats.setdefault(stream, IngestStats())
            stats.segments += 1
        for sid, sf in self.formats.items():
            t0 = time.perf_counter()
            blob = self.encode_format(frames_u8, src_f, sf)
            dt = time.perf_counter() - t0
            self.put_segment(stream, seg, sid, blob, encode_s=dt)

    # -- retrieval -------------------------------------------------------------
    def attach_retriever(self, retriever) -> None:
        """Install a cache-aware retrieval hook (repro.serving): ``retrieve``
        then routes through it, so every consumer of this store — including
        plain ``run_query`` — shares the serving layer's decoded-segment
        cache.  Pass ``None`` to restore direct decoding."""
        self._retriever = retriever

    def set_fallback(self, fallback) -> None:
        """Install a fallback-chain blob provider (repro.ingest.fallback):
        when a stored segment is missing — not yet materialized by the
        background transcoder, or reclaimed by erosion — ``_blob`` asks it
        to reconstruct the exact blob from the nearest richer ancestor on
        the format tree.  Pass ``None`` to restore strict reads."""
        self._fallback = fallback

    def _blob(self, stream: str, seg: int, sf_id: str
              ) -> tuple[bytes, bool]:
        """Fetch a stored blob, reconstructing via the fallback chain when
        the physical copy is absent.  Returns ``(blob, fallback)`` where
        ``fallback`` reports which path actually served the read.  Raises
        KeyError only when the chain (ultimately golden) cannot serve it
        either."""
        try:
            return self.backend.get(_sf_key(sf_id, stream, seg)), False
        except KeyError:
            if self._fallback is None:
                raise
            return self._fallback.reconstruct(self, stream, seg, sf_id), True

    def retrieve(self, stream: str, seg: int, sf_id: str,
                 cf: FidelityOption) -> tuple[np.ndarray, dict]:
        """Decode a stored segment (chunk-skip under the consumer's sparser
        sampling) and convert to the consumption fidelity.  Returns
        (frames_u8, timing/cost dict)."""
        if self._retriever is not None:
            return self._retriever(stream, seg, sf_id, cf)
        return self.retrieve_direct(stream, seg, sf_id, cf)

    def retrieve_direct(self, stream: str, seg: int, sf_id: str,
                        cf: FidelityOption) -> tuple[np.ndarray, dict]:
        """The uncached decode path (bypasses any attached retriever)."""
        want = self.want_indices(sf_id, cf)
        frames, cost = self.decode_for(stream, seg, sf_id, want)
        t0 = time.perf_counter()
        out = self.convert(frames, sf_id, cf)
        cost["convert_s"] = time.perf_counter() - t0
        return out, cost

    def retrieve_many(self, stream: str, segs: list[int], sf_id: str,
                      cf: FidelityOption) -> tuple[list[np.ndarray], dict]:
        """Retrieve several segments at one consumption fidelity.

        Amortizes the per-segment fixed costs: ``want_indices`` is computed
        once for the whole group, the chunk-skip *decode* of every segment
        runs as one batched dispatch (``decode_many_for`` stacks all wanted
        chunks), and the crop/resize ``convert`` runs as one fused call over
        the concatenated decode, then splits back per segment — decode and
        ``convert`` are per-frame programs, so results are bit-exact with
        ``retrieve``.  When
        a serving-layer retriever is attached, routes each segment through
        it instead (the decoded-segment cache owns reuse there).  Returns
        ``(frames_per_segment, aggregate_cost)``.
        """
        if self._retriever is not None:
            outs = [self._retriever(stream, s, sf_id, cf) for s in segs]
            cost = {"decode_s": 0.0, "convert_s": 0.0, "bytes": 0,
                    "chunks": 0, "frames": 0}
            for _, c in outs:
                for k in cost:
                    cost[k] += c.get(k, 0)
            return [f for f, _ in outs], cost
        cost = {"decode_s": 0.0, "convert_s": 0.0, "bytes": 0,
                "chunks": 0, "frames": 0}
        if not segs:
            return [], cost
        want = self.want_indices(sf_id, cf)
        decoded, c = self.decode_many_for(stream, segs, sf_id, want)
        for k in ("decode_s", "bytes", "chunks", "frames"):
            cost[k] += c[k]
        t0 = time.perf_counter()
        stacked = decoded[0] if len(decoded) == 1 else np.concatenate(decoded)
        conv = self.convert(stacked, sf_id, cf)
        cost["convert_s"] = time.perf_counter() - t0
        n = len(want)
        return [conv[i * n:(i + 1) * n] for i in range(len(segs))], cost

    # serving-layer primitives: retrieval = want_indices -> decode_for ->
    # convert.  The decoded-segment cache keeps decode_for outputs (frames on
    # the storage fidelity's grid) so any CF a cached decode covers is served
    # by the exact same convert() a direct retrieve would run — bit-exact
    # reuse by construction.
    def want_indices(self, sf_id: str, cf: FidelityOption) -> np.ndarray:
        """Stored-frame indices realizing ``cf``'s sampling (R1-checked)."""
        sf = self.formats[sf_id]
        if not sf.fidelity.richer_eq(cf):
            raise ValueError(
                f"R1 violated: SF {sf.fidelity.name()} poorer than CF {cf.name()}")
        return T.temporal_indices(sf.fidelity, cf, self.spec)

    def decode_for(self, stream: str, seg: int, sf_id: str,
                   want: np.ndarray) -> tuple[np.ndarray, dict]:
        """Fetch + chunk-skip-decode stored frames ``want`` at the storage
        fidelity's own grid (no consumption conversion).  The decode's own
        single header parse supplies the cost accounting, and ``bytes`` /
        ``chunks`` report what the decode actually touched — with v2 blobs
        a sparse read only pays for the chunks it lands in.  A missing blob
        is transparently served over the fallback chain when one is
        installed (``cost['fallback']`` flags it)."""
        blob, fb = self._blob(stream, seg, sf_id)
        t0 = time.perf_counter()
        with _span("codec.decode", sf=sf_id, seg=seg,
                   fallback=bool(fb)) as sp:
            frames, info = codec.decode_segment_ex(blob, np.asarray(want))
            sp.set(bytes=info["bytes"], chunks=info["chunks"],
                   frames=info["frames"])
        t_dec = time.perf_counter() - t0
        cost = {
            "decode_s": t_dec, "convert_s": 0.0, "bytes": info["bytes"],
            "chunks": info["chunks"], "frames": info["frames"],
        }
        if fb:
            cost["fallback"] = 1
        return frames, cost

    def decode_many_for(self, stream: str, segs: list[int], sf_id: str,
                        want: np.ndarray) -> tuple[list[np.ndarray], dict]:
        """Chunk-skip-decode ``want`` from several segments of one storage
        format in a single batched jit dispatch (``codec.decode_many``
        stacks every wanted chunk across the group), instead of one
        dispatch + host transfer per segment."""
        fetched = [self._blob(stream, s, sf_id) for s in segs]
        blobs = [b for b, _fb in fetched]
        t0 = time.perf_counter()
        with _span("codec.decode", sf=sf_id, segments=len(segs)) as sp:
            frames_list, info = codec.decode_many(blobs, np.asarray(want))
            sp.set(bytes=info["bytes"], chunks=info["chunks"],
                   frames=info["frames"])
        cost = {
            "decode_s": time.perf_counter() - t0, "convert_s": 0.0,
            "bytes": info["bytes"], "chunks": info["chunks"],
            "frames": info["frames"], "dispatches": info["dispatches"],
        }
        n_fb = sum(fb for _b, fb in fetched)
        if n_fb:
            cost["fallback"] = n_fb
        return frames_list, cost

    def convert(self, frames: np.ndarray, sf_id: str,
                cf: FidelityOption) -> np.ndarray:
        """Storage-grid frames -> consumption fidelity (crop + resize)."""
        sf = self.formats[sf_id]
        with _span("convert", sf=sf_id, cf=cf.name(), frames=len(frames)):
            return T.to_host(
                T.spatial_convert(frames, sf.fidelity, cf, self.spec))

    def has_segment(self, stream: str, seg: int, sf_id: str) -> bool:
        """Whether the blob is physically materialized (fallback excluded)."""
        return _sf_key(sf_id, stream, seg) in self.backend

    def can_serve(self, stream: str, seg: int, sf_id: str) -> bool:
        """Whether a retrieve would succeed: materialized, or reachable
        over the installed fallback chain."""
        if self.has_segment(stream, seg, sf_id):
            return True
        if self._fallback is None:
            return False
        return self._fallback.can_reconstruct(self, stream, seg, sf_id)

    def available_segments(self, stream: str, sf_id: str) -> list[int]:
        prefix = f"{stream}:{sf_id}:"
        return [int(k.rsplit(":", 1)[1]) for k in self.backend.keys(prefix)]

    # -- erosion ----------------------------------------------------------------
    def erode(self, stream: str, sf_id: str, fraction: float | None = None,
              seed: int = 0, *, segments: list[int] | None = None,
              count: int | None = None) -> ErodeResult:
        """Delete segments of this stream × format and account the bytes.

        Victims are chosen with a stratified deterministic spread across
        the (sorted) timeline — one per stratum at a seed-derived phase —
        so repeated erosion sweeps thin the format uniformly.  ``segments``
        restricts candidates (the erosion executor passes one age cohort);
        ``count`` deletes an exact number instead of a ``fraction`` of the
        candidates.  Returns an ``ErodeResult`` with segment, byte and
        chunk-span accounting (the bytes the executor reports reclaimed)."""
        cands = self.available_segments(stream, sf_id)
        if segments is not None:
            allowed = set(segments)
            cands = [s for s in cands if s in allowed]
        if count is None:
            if fraction is None:
                raise ValueError("erode needs fraction= or count=")
            count = int(round(len(cands) * fraction))
        res = ErodeResult()
        for s in stratified_pick(cands, count, seed):
            key = _sf_key(sf_id, stream, int(s))
            try:
                blob = self.backend.get(key)
            except KeyError:
                continue  # raced with a concurrent deleter; not ours
            chunks, chunk_bytes = blob_chunk_profile(blob)
            if self.backend.delete(key):
                res.segments += 1
                res.bytes += len(blob)
                res.chunks += chunks
                res.chunk_bytes += chunk_bytes
                res.victims.append(int(s))
        return res

    def segment_bytes(self, stream: str, seg: int, sf_id: str) -> int:
        """Stored size of one materialized blob, 0 when absent (eroded or
        not yet transcoded) — what predicate pushdown reports as bytes a
        pruned segment never read."""
        try:
            return self.backend.size_of(_sf_key(sf_id, stream, seg))
        except KeyError:
            return 0

    def storage_bytes(self, stream: str | None = None) -> int:
        return self.backend.total_bytes(f"{stream}:" if stream else "")

    def flush(self):
        self.backend.flush()
