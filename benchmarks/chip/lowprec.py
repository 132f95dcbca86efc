"""The reference of ``reference.py`` in JAX at a chosen precision, put in
the program's place: the control of the benchmark's comparison.

With ``dtype="bfloat16"`` (the nearest precision below the float32 the
codec, the conversion and the operators compute in) every layer a cell
checks is produced this way from the cell's own stored data, and judged by
the same functions and limits as a run of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import check as C
from . import harness as H
from . import reference as R
from . import traffic as T


# -- the reference in JAX, at a chosen precision ---------------------------------

@functools.partial(jax.jit, static_argnames=("k", "dtype"))
def _decode(sym, q, k: int, dtype: str):
    """Symbols (n, hb, wb, 8, 8) -> uint8 frames, every step in
    ``dtype``."""
    dt = jnp.dtype(dtype)
    d = jnp.asarray(R.dct_matrix(), dt)
    n, hb, wb = sym.shape[:3]
    coef = sym.astype(dt) * q.astype(dt)
    resid = jnp.einsum("ji,nabjk,kl->nabil", d, coef, d)
    resid = resid.transpose(0, 1, 3, 2, 4).reshape(n, hb * 8, wb * 8)

    def step(pred, xs):
        t, r = xs
        pred = jnp.where(t % k == 0, jnp.asarray(128, dt), pred)
        pred = jnp.clip(pred + r, 0, 255).astype(dt)
        return pred, jnp.round(pred.astype(jnp.float32)).astype(jnp.uint8)

    init = jnp.full((hb * 8, wb * 8), 128, dt)
    _, out = jax.lax.scan(step, init, (jnp.arange(n), resid))
    return out


@functools.partial(jax.jit, static_argnames=("k", "dtype"))
def _encode(frames, q, k: int, dtype: str):
    """uint8 frames -> int16 symbols, every step in ``dtype``."""
    dt = jnp.dtype(dtype)
    d = jnp.asarray(R.dct_matrix(), dt)
    qd = q.astype(dt)
    n, h, w = frames.shape

    def step(pred, xs):
        t, f = xs
        pred = jnp.where(t % k == 0, jnp.asarray(128, dt), pred)
        x = (f.astype(dt) - pred).reshape(h // 8, 8, w // 8, 8)
        coef = jnp.einsum("ij,ajbk,lk->abil", d, x, d)
        sym = jnp.round(coef / qd)
        resid = jnp.einsum("ji,abjk,kl->abil", d, sym * qd, d)
        pred = jnp.clip(pred + resid.transpose(0, 2, 1, 3).reshape(h, w),
                        0, 255).astype(dt)
        return pred, sym.astype(jnp.int16)

    init = jnp.full((h, w), 128, dt)
    _, out = jax.lax.scan(step, init, (jnp.arange(n), frames))
    return out


@functools.partial(jax.jit, static_argnames=("dtype",))
def _resize(frames, wy, wx, dtype: str):
    dt = jnp.dtype(dtype)
    x = jnp.einsum("nhw,hH,wW->nHW", frames.astype(dt), wy.astype(dt),
                   wx.astype(dt))
    return jnp.clip(jnp.round(x.astype(jnp.float32)), 0, 255).astype(
        jnp.uint8)


@functools.partial(jax.jit, static_argnames=("op", "dtype"))
def _stage0(frames, op: str, dtype: str):
    dt = jnp.dtype(dtype)
    x = frames.astype(dt) / jnp.asarray(255, dt)
    if op == "diff":
        return jnp.abs(x[1:] - x[:-1]).mean(axis=(1, 2), dtype=dt)
    ty, tx = R.MOTION_GRID
    e = (jnp.abs(x[:, 1:, :-1] - x[:, :-1, :-1])
         + jnp.abs(x[:, :-1, 1:] - x[:, :-1, :-1]))
    n, h, w = e.shape
    hh, ww = (h // ty) * ty, (w // tx) * tx
    return e[:, :hh, :ww].reshape(n, ty, hh // ty, tx, ww // tx).mean(
        axis=(2, 4), dtype=dt)


class Control:
    """The reference's layers in JAX at ``dtype``."""

    def __init__(self, dtype: str = "bfloat16"):
        self.dtype = dtype

    def decode_blob(self, blob: bytes):
        hdr, payload = R.parse_blob(blob)
        if hdr["raw"]:
            return R.decode_blob(blob)  # stored bytes: nothing to compute
        q = np.asarray(R.quant_steps(hdr["qs"]), np.float32)
        return np.asarray(_decode(R.blob_symbols(blob), q, k=hdr["k"],
                                  dtype=self.dtype))

    def encode_symbols(self, frames, quant_scale: float, keyframe: int):
        q = np.asarray(R.quant_steps(quant_scale), np.float32)
        return np.asarray(_encode(frames, q, k=keyframe, dtype=self.dtype))

    def convert_frames(self, frames, spec, fid_from, fid_to):
        _, h_to, w_to = R.resolve(spec, fid_to)
        _, h, w = frames.shape
        if (h, w) == (h_to, w_to):
            return np.asarray(frames)
        return np.asarray(_resize(
            frames, np.asarray(R.resize_weights(h, h_to), np.float32),
            np.asarray(R.resize_weights(w, w_to), np.float32),
            dtype=self.dtype))

    def stage0(self, op, frames, positions, crop, spec):
        raw = np.asarray(_stage0(frames, op=op, dtype=self.dtype),
                         np.float64)
        if op == "diff":
            return raw, R.diff_items(raw, positions, spec)
        return raw, R.motion_items(raw, positions, frames.shape[1:], crop,
                                   spec)


# -- the control in a cell's place -------------------------------------------------

def control_numbers(workload: str, seed: int, ctl: Control,
                    spec_override: dict | None = None) -> dict:
    """The numbers a run of ``workload`` compares, with the control
    producing every layer that the program would."""
    bench = H.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    cfg = H.load_config(cell["config"])
    if spec_override:
        cfg["spec"] = spec_override
    traffic = T.load(cell["traffic"])
    spec, dcfg, _server = H.program_objects(cfg)
    sp = cfg["spec"]
    pool = H.make_pool(spec, cfg, seed)
    fmts = dcfg.storage_formats()
    blobs = {(p, sid): H.program_encode(spec, f, fmts[sid])
             for p, f in enumerate(pool) for sid in fmts}
    t = C.Tally()
    if traffic["kind"] == "ingest":
        golden, node = H._golden_id(dcfg), H._golden_node(dcfg)
        g_f = H._fid(node.fidelity)
        for p, frames in enumerate(pool):
            ref_sym = R.encode_symbols(frames, node.fidelity.quant_scale,
                                       node.coding.keyframe)
            t.frames("symbol", ctl.encode_symbols(
                frames, node.fidelity.quant_scale, node.coding.keyframe),
                ref_sym)
            ref_full = R.decode_blob(blobs[p, golden])
            ctl_full = ctl.decode_blob(blobs[p, golden])
            for sid, sf in fmts.items():
                if sid == golden:
                    continue
                f = H._fid(sf.fidelity)
                rows = R.stored_rows(sp, g_f, f)
                t.frames("raw", ctl.convert_frames(ctl_full[rows], sp, g_f, f),
                         R.convert_frames(ref_full[rows], sp, g_f, f))
        return t.numbers(list(H.INGEST_CHECKS))
    stage0 = H.stage0_formats(cfg, dcfg, traffic)
    coded = any(not fmts[v[3]].coding.bypass for v in stage0.values())
    n_total = sp["fps"] * sp["segment_seconds"]
    for p in range(len(pool)):
        dec = {sid: (ctl.decode_blob(blobs[p, sid]),
                     R.decode_blob(blobs[p, sid]))
               for sid in {v[3] for v in stage0.values()}}
        for cf, (op, _n, cf_f, sf_id, sf_f) in stage0.items():
            # each layer against the reference given the control's own
            # input to that layer, as a run holds the program
            rows = R.stored_rows(sp, sf_f, cf_f)
            ctl_dec = dec[sf_id][0][rows]
            t.frames("decode", ctl_dec, dec[sf_id][1][rows])
            ctl_conv = ctl.convert_frames(ctl_dec, sp, sf_f, cf_f)
            t.frames("convert", ctl_conv,
                     R.convert_frames(ctl_dec, sp, sf_f, cf_f))
            pos = R.sample_positions(n_total, cf_f["sampling"])
            ref_raw, ref_items = R.stage0(op, ctl_conv, pos, cf_f["crop"],
                                          sp)
            ctl_raw, ctl_items = ctl.stage0(op, ctl_conv, pos, cf_f["crop"],
                                            sp)
            C.compare_stage0(t, op, ctl_raw, ctl_items, ref_raw, ref_items,
                             pos, sp)
    names = list(H.QUERY_CHECKS) + (list(H.DECODE_CHECKS) if coded else [])
    return t.numbers(names)
