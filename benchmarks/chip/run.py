#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks for.
Progress goes to standard error; the last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with its limit.  The same numbers are the last lines of
standard error.  Where JAX finds no accelerator, or fewer chips than the
cell asks for, or anything else fails, the exit code is 1 and no JSON line
is printed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# a fixed directory inside the checkout: only a cell's first run compiles
CACHE = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import jax
        jax.config.update("jax_compilation_cache_dir", CACHE)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        from benchmarks.chip import harness
        line, checks = harness.run_cell(args.workload, args.seed,
                                        args.seconds, bool(args.trace),
                                        T_START, log=log)
    except Exception:  # noqa: BLE001 - any failure: no result line
        traceback.print_exc()
        log("chipbench: FAILED, no result")
        return 1
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
