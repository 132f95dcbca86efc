"""The benchmark's float64 references agree with the program at the demo
camera grid, and the control (the reference computed in bfloat16 in the
program's place) fails the comparison in every cell."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import check as C  # noqa: E402
from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import reference as R  # noqa: E402

from repro.analytics import operators as ops  # noqa: E402
from repro.analytics.scene import generate_segment  # noqa: E402
from repro.codec import segment as codec  # noqa: E402
from repro.codec import transform as Tr  # noqa: E402
from repro.core.knobs import (FidelityOption, IngestSpec,  # noqa: E402
                              fidelity_space)

SPEC = IngestSpec()
SP = {"height": SPEC.height, "width": SPEC.width, "fps": SPEC.fps,
      "segment_seconds": SPEC.segment_seconds, "nominal": SPEC.nominal}
GOLDEN = FidelityOption()


def fid(f):
    return H._fid(f)


@pytest.fixture(scope="module")
def frames():
    return generate_segment("jackson", 3, SPEC)[0]


@pytest.fixture(scope="module")
def blob(frames):
    return codec.encode_segment(frames, quant_scale=1.0,
                                keyframe_interval=10, zstd_level=3)


def test_resolution_and_sampling_rules_match():
    for f in fidelity_space():
        assert R.resolve(SP, fid(f)) == SPEC.resolve(f)
    for f in fidelity_space()[::7]:
        for g in fidelity_space()[::11]:
            if f.richer_eq(g):
                np.testing.assert_array_equal(
                    R.stored_rows(SP, fid(f), fid(g)),
                    Tr.temporal_indices(f, g, SPEC))


def test_decode_agrees(blob):
    got = codec.decode_segment(blob)
    ref = R.decode_blob(blob)
    d = np.abs(got.astype(int) - ref)
    assert d.max() <= 1 and np.mean(d > 0) < 1e-3


def test_raw_decode_is_the_bytes(frames):
    np.testing.assert_array_equal(R.decode_blob(codec.encode_raw(frames)),
                                  frames)


def test_encode_agrees(frames, blob):
    got = R.blob_symbols(blob)
    ref = R.encode_symbols(frames, 1.0, 10)
    assert np.mean(got != ref) < 1e-2
    assert np.abs(got.astype(int) - ref).max() <= 2


@pytest.mark.parametrize("res,crop", [(270, 1.0), (180, 1.0), (540, 0.75),
                                      (360, 0.5)])
def test_convert_agrees(frames, res, crop):
    cf = FidelityOption("good", crop, res, 0.5)
    rows = R.stored_rows(SP, fid(GOLDEN), fid(cf))
    got = np.asarray(Tr.spatial_convert(frames[rows], GOLDEN, cf, SPEC))
    ref = R.convert_frames(frames[rows], SP, fid(GOLDEN), fid(cf))
    assert got.shape == ref.shape
    d = np.abs(got.astype(int) - ref)
    assert d.max() <= 1 and np.mean(d > 0) < 1e-2


@pytest.mark.parametrize("op", ["diff", "motion"])
def test_stage0_agrees(frames, op):
    cf = FidelityOption("good", 1.0, 360, 0.5)
    rows = R.stored_rows(SP, fid(GOLDEN), fid(cf))
    conv = R.convert_frames(frames[rows], SP, fid(GOLDEN), fid(cf))
    pos = R.sample_positions(SPEC.frames_per_segment, 0.5)
    raw, items = R.stage0(op, conv, pos, 1.0, SP)
    x = np.asarray(conv, np.float32) / 255.0
    got = np.asarray(ops._diff_scores(x) if op == "diff"
                     else ops._motion_tiles(x, *R.MOTION_GRID))
    assert np.abs(got - raw).max() / R.THRESHOLD[op] < 1e-4
    assert ops.OPERATORS[op].detect(conv, cf, SPEC, positions=pos) == items


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  H.load_benchmark()["workloads"]])
def test_lower_precision_fails(cell):
    from benchmarks.chip import lowprec
    ok, table = C.verdict(lowprec.control_numbers(
        cell, 11, lowprec.Control("bfloat16"), spec_override=SP))
    assert not ok, table
    ok32, table32 = C.verdict(lowprec.control_numbers(
        cell, 11, lowprec.Control("float32"), spec_override=SP))
    assert ok32, table32
