"""Reference logits of a plan's batches, and the gap of served rows.

For each batch the reference builds its own induced subgraph from the
benchmark's graph and the batch's membership, and runs its plain forward.
Edges are padded to one count with weight-0 edges on an extra dummy row,
so one compiled reference serves every batch.

The number compared is the worst row's gap: ``max_c |z - z_ref|`` over the
row's largest reference logit magnitude (floored at a thousandth of the
largest over all rows compared).
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from ibmb_bench import data
from ibmb_bench.reference import common, graph as ref_graph


def _padded(batch: dict, n_edges: int) -> dict:
    n = batch["features"].shape[0]
    pad = n_edges - len(batch["row"])
    feats = np.concatenate([batch["features"],
                            np.zeros((1, batch["features"].shape[1]),
                                     np.float32)])
    fill = np.full(pad, n, np.int32)
    return dict(features=feats,
                row=np.concatenate([batch["row"], fill]),
                col=np.concatenate([batch["col"], fill]),
                weight=np.concatenate([batch["weight"],
                                       np.zeros(pad, np.float32)]))


def _forward(forward, model_key, dtype, params, batch):
    return forward(dict(model_key), params, batch, dtype)


def reference_rows(model: dict, params, g, plan, batches,
                   dtype=common.F32) -> Dict[int, np.ndarray]:
    """``{global output id: reference logits}`` over ``batches`` (the first
    batch that holds an output answers for it, as the plan routes)."""
    from ibmb_bench.reference import gat, gcn
    ref = {"gcn": gcn, "gat": gat}[model["kind"]]
    ng = ref_graph.normalized_graph(g.src, g.dst, g.num_nodes)
    fwd = common.jitted(_forward, ref.forward, tuple(sorted(model.items())),
                        dtype)
    params = common.cast(params, dtype)
    built = {b: data.reference_batch(g, ng, plan, b) for b in batches}
    n_edges = max(len(x["row"]) for x in built.values())
    rows: Dict[int, np.ndarray] = {}
    for b in sorted(built):
        ids, outs = data.batch_membership(plan, b)
        x = built[b]
        logits = np.asarray(jnp.asarray(
            fwd(params, {k: jnp.asarray(v) for k, v in
                         _padded(x, n_edges).items()}), jnp.float32))
        for node, r in zip(outs, logits[x["out"]]):
            rows.setdefault(int(node), r)
    return rows


def rows_of(ids: np.ndarray, ref_rows: Dict[int, np.ndarray]) -> np.ndarray:
    return np.stack([ref_rows[int(i)] for i in ids]).astype(np.float64)


def worst_row_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """Worst row's gap of ``got`` against the reference rows ``ref``."""
    got = np.asarray(got, np.float64)
    scale = np.abs(ref).max(axis=1)
    scale = np.maximum(scale, 1e-3 * scale.max())
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf")
    return float((np.abs(got - ref).max(axis=1) / scale).max())
