"""Shared by the benchmark's CPU tests: a checkout-shaped root in a
temporary directory with the benchmark's cells cut to a size the CPU runs
in seconds (600 nodes, hidden width 32, 64 outputs per batch).

The serving cell ``gat-products.serve`` has its files under ``ibmb_bench/``
but is not yet in ``BENCHMARK.json`` (it has not been measured on the
chip); ``serve_cell.json`` holds its entries, and the temporary roots get
them, so the serving mode stays under test."""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ibmb_bench import harness  # noqa: E402

TINY = dict(num_nodes=600, hidden=32, max_outputs_per_batch=64)


def make_root(dest: str, tiny: bool = True, num_nodes: int = 0) -> str:
    """Copy ``BENCHMARK.json`` and ``ibmb_bench/`` to ``dest`` and shrink
    every configuration there; workloads, modes, metrics and limits stay
    as committed. ``tiny=False`` keeps the published widths and degrees and
    cuts only the node count, to ``num_nodes``."""
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    shutil.copytree(os.path.join(REPO, "ibmb_bench"),
                    os.path.join(dest, "ibmb_bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(REPO, c["file"]))
        if tiny:
            cfg["num_nodes"] = TINY["num_nodes"]
            cfg["avg_degree"] = min(cfg["avg_degree"], 8.0)
            cfg["model"]["hidden"] = TINY["hidden"]
        else:
            cfg["num_nodes"] = num_nodes
        cfg["ibmb"]["max_outputs_per_batch"] = TINY["max_outputs_per_batch"]
        with open(os.path.join(dest, c["file"]), "w") as f:
            json.dump(cfg, f)
    serve = harness.load_json(os.path.join(HERE, "serve_cell.json"))
    if serve["cell"]["name"] not in [w["name"] for w in bench["workloads"]]:
        bench["workloads"].append(serve["cell"])
        bench["end_to_end"] += serve["end_to_end"]
        bench["per_layer"] += serve["per_layer"]
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


def run(root: str, cell: str, seed: int = 2**31 + 11, seconds: float = 1.0,
        trace: int = 0):
    args = harness.parse_args(["--workload", cell, "--seed", str(seed),
                               "--seconds", str(seconds),
                               "--trace", str(trace)])
    return harness.run_cell(args, root=root, on_chip=False)
