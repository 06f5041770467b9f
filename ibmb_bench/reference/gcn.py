"""Plain GCN (Kipf & Welling; IBMB App. B: LayerNorm, ReLU, dropout).

Layer l: ``H' = Â (H W) + b`` with ``Â = D^-1/2 (A + I) D^-1/2`` over the
batch's induced subgraph and global degrees; every layer but the last is
followed by LayerNorm, ReLU and dropout. Aggregation is a segment sum over
the reference's own edge list.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ibmb_bench.reference import common


def init_params(model: dict, key, random_affine: bool = False):
    """Glorot-uniform weights drawn from ``key`` as the program draws them
    (one 6-way split per layer, the weight from the first sub-key), zero
    biases, unit LayerNorm. ``random_affine`` draws biases and LayerNorm
    parameters too, for served weights."""
    dims = ([model["in_dim"]] + [model["hidden"]] * (model["num_layers"] - 1)
            + [model["out_dim"]])
    layers = []
    for l in range(model["num_layers"]):
        key, *ks = jax.random.split(key, 6)
        d_in, d_out = dims[l], dims[l + 1]
        p = {"w": common.glorot(ks[0], (d_in, d_out)),
             "b": jnp.zeros((d_out,), common.F32)}
        if l < model["num_layers"] - 1:
            p["ln_scale"] = jnp.ones((d_out,), common.F32)
            p["ln_bias"] = jnp.zeros((d_out,), common.F32)
        if random_affine:
            p = _randomize_affine(p, ks[4])
        layers.append(p)
    return {"layers": layers}


def _randomize_affine(p, key):
    out = dict(p)
    for i, name in enumerate(("b", "ln_bias", "ln_scale")):
        if name in p:
            noise = 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            p[name].shape, common.F32)
            out[name] = p[name] + noise
    return out


def forward(model: dict, params, batch, dtype=common.F32, key=None):
    """Logits of every batch row; ``key`` switches dropout on."""
    n = batch["features"].shape[0]
    h = batch["features"].astype(dtype)
    w_edge = batch["weight"].astype(dtype)
    last = len(params["layers"]) - 1
    for l, p in enumerate(params["layers"]):
        h = common.matmul(h, p["w"], dtype)
        h = jax.ops.segment_sum(h[batch["col"]] * w_edge[:, None],
                                batch["row"], num_segments=n) + p["b"]
        if l < last:
            h, key = common.hidden_activation(h, p, key, model["dropout"])
    return h


def loss(model: dict, params, batch, dtype=common.F32, key=None):
    logits = forward(model, params, batch, dtype, key)
    return common.cross_entropy(logits[batch["out"]], batch["labels"])
