"""The model work a batch needs, counted from its real sizes.

Each model kind counts its own work in its reference module
(``reference/<kind>.py``): ``forward_flops(model, n, e)`` is the matrix
work of one forward over a batch with ``n`` real nodes and ``e`` real edges
(self loops included). Padding counts for nothing. LayerNorm, ReLU,
dropout, the edge softmax and the loss are left out: they are a small share
and not matrix work. A training step counts three forwards (forward, and a
backward of twice its work). The share of the peak is taken against the
chip's published bf16 peak, since no float32 peak is published; the
program computes in float32.
"""
from __future__ import annotations

import numpy as np

from ibmb_bench import reference


def forward_flops(model: dict, n: int, e: int) -> int:
    return int(reference.for_model(model).forward_flops(model, n, e))


def train_step_flops(model: dict, n: int, e: int) -> int:
    return 3 * forward_flops(model, n, e)


def real_counts(fields: dict) -> tuple:
    """Per-batch real (nodes, edges) of a plan's stacked cache fields."""
    nodes = (np.asarray(fields["node_mask"]) > 0).sum(axis=1)
    edges = (np.asarray(fields["edge_weight"]) != 0).sum(axis=1)
    return nodes.astype(np.int64), edges.astype(np.int64)
