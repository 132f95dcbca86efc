#!/usr/bin/env python3
"""The control of the benchmark's comparison, and the program's readings
on many seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        [--readings 4,5,6 --seconds 8] [--dtype bfloat16]

The control is the reference of ``reference.py`` put in the program's
place and computed in the nearest precision below the float32 the
configuration's codec, conversion and operators state: bfloat16, in JAX on
whatever device runs it.  For each of ``--seeds`` it builds the cell's
stored data from the seed as a run does (the pooled segments, encoded by
the program), computes every layer the cell checks with the control
(decode, conversion, stage-0 operators; or, for an ingest cell, the
encoded symbols and the derived format), and prints the numbers the
cell's check compares, judged by the same functions and limits.  Each of
``--readings`` is one whole run of the cell (``harness.run_cell``) with a
window of ``--seconds``, printing the same numbers for the program.  One
JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--readings", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmarks.chip import lowprec
    from benchmarks.chip import harness as H
    dev = jax.devices()[0]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds:
        t0 = time.perf_counter()
        nums = lowprec.control_numbers(args.workload, seed,
                                       lowprec.Control(args.dtype))
        print(json.dumps({"kind": "control", "dtype": args.dtype,
                          "workload": args.workload, "seed": seed,
                          "device": dev.device_kind, "numbers": nums,
                          "seconds": time.perf_counter() - t0}), flush=True)
    for seed in [int(s) for s in args.readings.split(",") if s]:
        t0 = time.perf_counter()
        line, checks = H.run_cell(args.workload, seed, args.seconds, False,
                                  t0, log=lambda m: print(m, file=sys.stderr,
                                                          flush=True))
        print(json.dumps({"kind": "program", "workload": args.workload,
                          "seed": seed, "device": dev.device_kind,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "numbers": {k: v["value"]
                                      for k, v in checks.items()},
                          "metrics": {k: v["value"] for k, v in
                                      line["metrics"].items()},
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
