"""Reference batches, built from the benchmark's own graph.

A batch of the program's plan is defined by two lists of global node ids:
its nodes, in the plan's local order (``-1`` marks a padded slot), and which
of them are outputs. Everything else the reference needs is built here from
the generator's raw edge list: the undirected graph with self loops, the
symmetric GCN normalisation ``D^-1/2 (A + I) D^-1/2`` with global degrees
(IBMB App. B), the features and the labels. Nothing the program computed is
read.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass(frozen=True)
class NormGraph:
    rows: np.ndarray      # (M,) int64, every undirected pair both ways + self loops
    cols: np.ndarray      # (M,) int64
    weight: np.ndarray    # (M,) float32, 1/sqrt(deg_row * deg_col)
    num_nodes: int


def normalized_graph(src: np.ndarray, dst: np.ndarray, n: int) -> NormGraph:
    """Undirected, deduplicated, with self loops and symmetric weights."""
    loops = np.arange(n, dtype=np.int64)
    a = np.concatenate([src.astype(np.int64), dst.astype(np.int64), loops])
    b = np.concatenate([dst.astype(np.int64), src.astype(np.int64), loops])
    key = np.unique(a * n + b)
    rows, cols = key // n, key % n
    deg = np.bincount(rows, minlength=n).astype(np.float64)
    w = 1.0 / np.sqrt(deg[rows] * deg[cols])
    return NormGraph(rows, cols, w.astype(np.float32), n)


def build_batch(g: NormGraph, features: np.ndarray, labels: np.ndarray,
                node_ids: np.ndarray, output_ids: np.ndarray) -> Dict:
    """The induced subgraph of ``g`` on ``node_ids`` (plan order, -1 pads).

    Returns host arrays: ``features`` (N_pad, F) with zero padded rows,
    local ``row``/``col``/``weight`` of every edge between batch nodes,
    ``out`` the local rows of ``output_ids`` and their ``labels``.
    """
    node_ids = np.asarray(node_ids, np.int64)
    real = node_ids >= 0
    pos = np.full(g.num_nodes, -1, np.int64)
    pos[node_ids[real]] = np.nonzero(real)[0]
    keep = (pos[g.rows] >= 0) & (pos[g.cols] >= 0)
    feats = np.zeros((len(node_ids), features.shape[1]), np.float32)
    feats[real] = features[node_ids[real]]
    out = pos[np.asarray(output_ids, np.int64)]
    if (out < 0).any():
        raise ValueError("an output node is not among the batch's nodes")
    return dict(features=feats,
                row=pos[g.rows[keep]].astype(np.int32),
                col=pos[g.cols[keep]].astype(np.int32),
                weight=g.weight[keep],
                out=out.astype(np.int32),
                labels=np.asarray(labels)[output_ids].astype(np.int32))
