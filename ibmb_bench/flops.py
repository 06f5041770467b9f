"""The model work a batch needs, counted from its real sizes.

A layer with ``n`` real nodes, ``e`` real edges (self loops included),
input width ``d_in`` and output width ``d_out`` counts

* GCN: ``2·n·d_in·d_out`` for the transform and ``2·e·d_out`` for the
  weighted aggregation, which runs at the output width;
* GAT (``H`` heads of width ``d``, ``w = H·d``): ``2·n·d_in·w`` for the
  transform, ``4·n·w`` for the two attention products with ``a_src`` and
  ``a_dst``, and ``2·e·w`` for the attention-weighted sum over edges.

Padding counts for nothing. LayerNorm, ReLU, dropout, the edge softmax and
the loss are left out: they are a small share and not matrix work. A
training step counts three forwards (forward, and a backward of twice its
work). The share of the peak is taken against the chip's published bf16
peak, since no float32 peak is published; the program computes in float32.
"""
from __future__ import annotations

import numpy as np


def layer_widths(model: dict):
    """(d_in, d_out) per layer, with d_out the width the layer aggregates."""
    dims = ([model["in_dim"]] + [model["hidden"]] * (model["num_layers"] - 1)
            + [model["out_dim"]])
    out = []
    for l in range(model["num_layers"]):
        d_in, d_out = dims[l], dims[l + 1]
        if model["kind"] == "gat" and l == model["num_layers"] - 1:
            d_out = model["heads"] * d_out        # heads averaged after
        out.append((d_in, d_out))
    return out


def forward_flops(model: dict, n: int, e: int) -> int:
    total = 0
    for d_in, w in layer_widths(model):
        total += 2 * n * d_in * w
        if model["kind"] == "gcn":
            total += 2 * e * w
        elif model["kind"] == "gat":
            total += 4 * n * w + 2 * e * w
        else:
            raise ValueError(f"no work count for model kind {model['kind']!r}")
    return int(total)


def train_step_flops(model: dict, n: int, e: int) -> int:
    return 3 * forward_flops(model, n, e)


def real_counts(fields: dict) -> tuple:
    """Per-batch real (nodes, edges) of a plan's stacked cache fields."""
    nodes = (np.asarray(fields["node_mask"]) > 0).sum(axis=1)
    edges = (np.asarray(fields["edge_weight"]) != 0).sum(axis=1)
    return nodes.astype(np.int64), edges.astype(np.int64)
