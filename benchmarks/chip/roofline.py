"""The least time a kernel's work can take on a chip, from its shapes and
the table of published peaks (``peaks.json``, keyed by ``device_kind``)."""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(table)})")
    return table[device_kind]


def residual_decode_work(frames: int, height: int, width: int
                         ) -> tuple[float, float]:
    """``(bytes, flops)`` the residual decode of ``frames`` frames of
    ``height x width`` cannot do without: read each int16 symbol once and
    write each uint8 pixel once; the separable 8-point inverse DCT costs
    8 multiply-adds per pixel along each axis (32 floating-point
    operations a pixel), the dequantize one multiply."""
    px = float(frames) * height * width
    return 3.0 * px, 33.0 * px


def least_time_s(nbytes: float, flops: float, device_kind: str
                 ) -> tuple[float, str]:
    """The larger of the bytes bound and the operations bound, and which
    of the two it is."""
    p = peaks(device_kind)
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    t_ops = flops / p["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
