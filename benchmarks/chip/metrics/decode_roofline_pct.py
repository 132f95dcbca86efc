"""Codec transform on the device: the least time the residual decode's
work could take (``roofline.least_time_s`` of the bytes it must move and
the operations it must do, from the golden format's shapes) over the
device time of the decode programs (``_chunk_residuals`` and
``_residuals_scan``, by their jit names) in the traced slice.  Over the
chips of a sharded cell: the least time of all their decodes / all their
device time."""

from benchmarks.chip import reference as R
from benchmarks.chip import roofline
from benchmarks.chip import tracereduce as tr

PROGRAMS = ("_chunk_residuals", "_residuals_scan")


def read(run):
    traces = run.traces or run.trace
    if not traces:
        return None
    runs = tr.module_runs(traces, PROGRAMS)
    calls = sum(1 for n, _s, _d in runs
                if tr.module_name(n) == "jit_" + PROGRAMS[0])
    dev_s = sum(d for _n, _s, d in runs) / 1e9
    if not calls or dev_s <= 0:
        return None
    node = [n for n in run.config["derived_config"]["nodes"]
            if n["golden"]][0]
    q, crop, res, samp = node["fidelity"]
    n, h, w = R.resolve(run.config["spec"], {
        "quality": q, "crop": crop, "resolution": res, "sampling": samp})
    k = min(node["coding"][1], n)
    chunks = -(-n // k)
    frames = k * (1 << max(0, chunks - 1).bit_length())  # padded stack
    nbytes, flops = roofline.residual_decode_work(calls * frames, h, w)
    least, _bound = roofline.least_time_s(nbytes, flops, run.device_kind)
    return 100.0 * least / dev_s
