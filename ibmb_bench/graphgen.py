"""The benchmark's own graph generator: a degree-corrected stochastic block
model (DC-SBM) stand-in for an OGB node-property dataset.

Communities are classes, so the graph is homophilic by construction; degree
propensities are lognormal, so degrees are heavy-tailed; features are the
class centroid plus Gaussian noise. Everything is drawn from the
configuration's own ``graph_seed``, never from a run's ``--seed``: the graph
is part of the configuration.

This is a copy of the sampler in ``src/repro/graph/synthetic.py``, kept here
so that the data a cell runs on cannot change with the program. It returns
the raw directed edge list; the program and the reference each build what
they need from it.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Dict

import numpy as np

GRAPH_KEYS = ("num_nodes", "num_classes", "avg_degree", "feat_dim",
              "homophily", "train_frac", "val_frac", "test_frac", "noise",
              "graph_seed")


@dataclasses.dataclass(frozen=True)
class GraphData:
    src: np.ndarray          # (E,) int32, directed sampled edges, no self loops
    dst: np.ndarray          # (E,) int32
    features: np.ndarray     # (N, F) float32
    labels: np.ndarray       # (N,) int32
    splits: Dict[str, np.ndarray]   # sorted int32 node ids per split

    @property
    def num_nodes(self) -> int:
        return len(self.labels)


def graph_spec(config: dict) -> dict:
    """The generator's parameters, read from a configuration file."""
    return {k: config[k] for k in GRAPH_KEYS}


def spec_digest(spec: dict) -> str:
    blob = json.dumps(spec, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def _sample_edges(spec: dict, rng: np.random.Generator):
    n, k = int(spec["num_nodes"]), int(spec["num_classes"])
    labels = rng.integers(0, k, size=n)
    theta = rng.lognormal(mean=0.0, sigma=1.0, size=n)
    order = np.argsort(labels, kind="stable")
    sorted_labels = labels[order]
    starts = np.searchsorted(sorted_labels, np.arange(k))
    ends = np.searchsorted(sorted_labels, np.arange(k), side="right")
    block_nodes = [order[starts[b]:ends[b]] for b in range(k)]
    block_probs = []
    for b in range(k):
        p = theta[block_nodes[b]]
        block_probs.append(p / p.sum() if p.sum() > 0 else None)

    num_edges = int(n * float(spec["avg_degree"]) / 2)
    p_global = theta / theta.sum()
    src = rng.choice(n, size=num_edges, p=p_global)
    intra = rng.random(num_edges) < float(spec["homophily"])
    dst = np.empty(num_edges, dtype=np.int64)
    for b in range(k):
        mask = intra & (labels[src] == b)
        cnt = int(mask.sum())
        if cnt and len(block_nodes[b]):
            dst[mask] = rng.choice(block_nodes[b], size=cnt, p=block_probs[b])
        elif cnt:
            dst[mask] = rng.choice(n, size=cnt, p=p_global)
    cnt = int((~intra).sum())
    if cnt:
        dst[~intra] = rng.choice(n, size=cnt, p=p_global)
    keep = src != dst
    return (src[keep].astype(np.int32), dst[keep].astype(np.int32),
            labels.astype(np.int32))


def generate(spec: dict) -> GraphData:
    """Sample the graph, features, labels and splits of ``spec``."""
    rng = np.random.default_rng(int(spec["graph_seed"]))
    n = int(spec["num_nodes"])
    src, dst, labels = _sample_edges(spec, rng)
    centroids = rng.normal(size=(int(spec["num_classes"]),
                                 int(spec["feat_dim"]))).astype(np.float32)
    feats = centroids[labels] + float(spec["noise"]) * rng.normal(
        size=(n, int(spec["feat_dim"]))).astype(np.float32)
    perm = rng.permutation(n)
    n_tr = int(float(spec["train_frac"]) * n)
    n_va = int(float(spec["val_frac"]) * n)
    n_te = int(float(spec["test_frac"]) * n)
    splits = {
        "train": np.sort(perm[:n_tr]).astype(np.int32),
        "val": np.sort(perm[n_tr:n_tr + n_va]).astype(np.int32),
        "test": np.sort(perm[n_tr + n_va:n_tr + n_va + n_te]).astype(np.int32),
    }
    return GraphData(src, dst, feats.astype(np.float32), labels, splits)


def save(data: GraphData, path: str) -> None:
    import os
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, src=data.src, dst=data.dst, features=data.features,
                 labels=data.labels, **{"split_" + k: v
                                        for k, v in data.splits.items()})
    os.replace(tmp, path)


def load(path: str) -> GraphData:
    with np.load(path) as z:
        splits = {k[len("split_"):]: z[k] for k in z.files
                  if k.startswith("split_")}
        return GraphData(z["src"], z["dst"], z["features"], z["labels"],
                         splits)
