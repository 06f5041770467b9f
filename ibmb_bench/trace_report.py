#!/usr/bin/env python3
"""Print where a traced run's time went, by the program's own spans and
scopes, as one JSON object.

    python ibmb_bench/trace_report.py ibmb_bench/.cache/traces/<cell> [--devices N]

Run it after ``run.py --trace 1``, whose trace it reads. It prints the
window, device busy time and the idle seconds by host label
(``trace_reduce``), beside ``program_trace``'s summary: the ``ibmb/``
spans, the device seconds per scope, how many idle seconds lie inside
some ``ibmb/`` span, the harness's ``bench/`` spans and the ops with the
most device time that carry no known scope.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from ibmb_bench import program_trace, trace_reduce  # noqa: E402


def report(trace_dir: str, n_devices: int = 1) -> dict:
    base = trace_reduce.summarize(trace_dir, n_devices)
    got = program_trace.summarize(trace_reduce.find_xplane(trace_dir),
                                  n_devices)
    idle = base["window_s"] - base["busy_s"]
    inside = got.pop("idle_in_spans_s")
    del got["path_seconds"]
    return dict(
        busy_s=base["busy_s"], idle_s=idle, idle_in_ibmb_spans_s=inside,
        idle_in_ibmb_spans_share=inside / idle if idle > 0 else None,
        idle_gaps=base["idle_gaps"], **got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)
    print(json.dumps(report(args.trace_dir, args.devices)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
