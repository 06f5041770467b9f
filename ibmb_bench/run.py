#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python ibmb_bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (dataset and plans from the cache under ``ibmb_bench/.cache/`` or
built there, weights from ``--seed``, every shape warmed up) is timed from
process start as ``setup_s``; then the cell's mode measures for
``--seconds``. With ``--trace 1`` the window runs under JAX's profiler and
the result carries the per-layer metrics instead of the end-to-end ones.
Exits non-zero, printing no result, when JAX finds no TPU.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from ibmb_bench import harness  # noqa: E402


def main(argv=None) -> int:
    args = harness.parse_args(argv)
    try:
        result = harness.run_cell(args, root=ROOT, t_start=T_START)
    except harness.NoChip as e:
        print(f"ibmb_bench: {e}", file=sys.stderr)
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
