"""``generate_segment`` renders a frame at a time: the same bytes as the
whole-stack formula, without its float stacks."""

import tracemalloc

import numpy as np
import pytest

from repro.analytics import scene
from repro.analytics.scene import STREAMS, generate_segment
from repro.core.knobs import IngestSpec

SMALL = IngestSpec(height=96, width=160, fps=8, segment_seconds=4)


def stacked(stream, seg, spec):
    """The whole-stack formula: every frame drawn into one float32 stack,
    the noise of all frames drawn at once, then clipped and cast."""
    n, h, w = spec.frames_per_segment, spec.height, spec.width
    rate, speed, pan, plate_p = STREAMS.get(stream, STREAMS["tucson"])
    rng = np.random.default_rng(scene._stream_seed(stream) * 1000003 + seg)
    bg = scene._background(stream, h, w + int(abs(pan) * n) + 8)
    cars = []
    for c in range(rng.poisson(rate)):
        ch = int(rng.integers(max(18, h // 4), max(24, h // 2)))
        cw = int(ch * rng.uniform(1.3, 1.7))
        lane_y = int(rng.uniform(0.45, max(0.451, 0.95 - ch / h)) * h)
        v = speed * rng.uniform(0.7, 1.4) * rng.choice([-1.0, 1.0])
        x_start = (-cw - rng.uniform(0, w * 0.5)) if v > 0 else \
            (w + rng.uniform(0, w * 0.5))
        shade = rng.uniform(140, 220)
        digits = "".join(str(d) for d in rng.integers(0, 10, 4))
        has_plate = rng.random() < plate_p
        cars.append((ch, cw, lane_y, v, x_start, shade, digits, has_plate))
    frames = np.empty((n, h, w), np.float32)
    noise = rng.normal(0, 2.0, (n, h, w)).astype(np.float32)
    for t in range(n):
        off = int(round(pan * t))
        frame = bg[:, off:off + w].copy()
        for ch, cw, ly, v, xs, shade, digits, has_plate in cars:
            scene._draw_car(frame, ly, int(round(xs + v * t)), ch, cw,
                            shade, digits, has_plate)
        frames[t] = frame
    return (frames + noise).clip(0, 255).astype(np.uint8)


@pytest.mark.parametrize("stream", sorted(STREAMS))
@pytest.mark.parametrize("seg", [0, 7, 123457])
def test_same_bytes_as_the_stacked_formula(stream, seg):
    got, truth = generate_segment(stream, seg, SMALL)
    assert got.dtype == np.uint8 and got.shape == (32, 96, 160)
    assert np.array_equal(got, stacked(stream, seg, SMALL))
    assert truth.stream == stream and truth.seg == seg


def test_same_bytes_at_720p():
    spec = IngestSpec(height=720, width=1280, fps=4, segment_seconds=4)
    got, _ = generate_segment("jackson", 3100000007 * 4, spec)
    assert got.shape == (16, 720, 1280)
    assert np.array_equal(got, stacked("jackson", 3100000007 * 4, spec))


def _peak_bytes(fn):
    tracemalloc.start()
    try:
        fn()
        return tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()


def test_peak_memory_is_a_frame_not_a_stack():
    spec = IngestSpec(height=240, width=320, fps=8, segment_seconds=4)
    n, h, w = spec.frames_per_segment, spec.height, spec.width
    out = n * h * w                       # the uint8 segment
    frame64 = h * w * 8
    assert _peak_bytes(lambda: generate_segment("jackson", 5, spec)) \
        < out + 8 * frame64
    # the stacked formula holds float stacks of the whole segment
    assert _peak_bytes(lambda: stacked("jackson", 5, spec)) > 10 * out
