"""Plain GAT (Veličković et al.; IBMB App. B: LayerNorm, ReLU, dropout).

Layer l, with H heads of width d: ``z = h W`` split into heads; for an edge
from node i to neighbour j (self loops included) the attention logit is
``LeakyReLU_0.2(a_src·z_i + a_dst·z_j)``, normalised by a softmax over
i's edges; ``out_i = Σ_j α_ij z_j``. Hidden layers concatenate the heads,
the output layer averages them; then the bias. Hidden layers are followed
by LayerNorm, ReLU and dropout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ibmb_bench.reference import common

SMALL = dict(kind="gat", in_dim=16, hidden=32, out_dim=5, num_layers=3,
             heads=4, dropout=0.3, dtype="float32")


def init_params(model: dict, key, random_affine: bool = False):
    """Glorot-uniform ``w``, ``a_src``, ``a_dst`` (sub-keys 0-2 of a 6-way
    split per layer), zero biases, unit LayerNorm; ``random_affine`` draws
    biases and LayerNorm parameters too, for served weights."""
    dims, heads = common.dims(model), model["heads"]
    layers = []
    for l in range(model["num_layers"]):
        key, *ks = jax.random.split(key, 6)
        d_in, d_out = dims[l], dims[l + 1]
        hidden = l < model["num_layers"] - 1
        dh = d_out // heads if hidden else d_out
        p = {"w": common.glorot(ks[0], (d_in, heads * dh)),
             "a_src": common.glorot(ks[1], (heads, dh)),
             "a_dst": common.glorot(ks[2], (heads, dh)),
             "b": jnp.zeros((heads * dh if hidden else d_out,), common.F32)}
        if hidden:
            p["ln_scale"] = jnp.ones((d_out,), common.F32)
            p["ln_bias"] = jnp.zeros((d_out,), common.F32)
        if random_affine:
            for i, name in enumerate(("b", "ln_bias", "ln_scale")):
                if name in p:
                    p[name] = p[name] + 0.1 * jax.random.normal(
                        jax.random.fold_in(ks[4], i), p[name].shape,
                        common.F32)
        layers.append(p)
    return {"layers": layers}


def _segment_softmax(logits, seg, n):
    top = jax.ops.segment_max(logits, seg, num_segments=n)
    ex = jnp.exp(logits - top[seg])
    den = jax.ops.segment_sum(ex, seg, num_segments=n)
    return ex / den[seg]


def forward(model: dict, params, batch, dtype=common.F32, key=None):
    """Logits of every batch row; ``key`` switches dropout on."""
    n = batch["features"].shape[0]
    row, col = batch["row"], batch["col"]
    h = batch["features"].astype(dtype)
    last = len(params["layers"]) - 1
    for l, p in enumerate(params["layers"]):
        heads, dh = p["a_src"].shape
        z = common.matmul(h, p["w"], dtype).reshape(n, heads, dh)
        e_src = (z * p["a_src"][None]).sum(-1)
        e_dst = (z * p["a_dst"][None]).sum(-1)
        att = _segment_softmax(
            jax.nn.leaky_relu(e_src[row] + e_dst[col], 0.2), row, n)
        out = jax.ops.segment_sum(z[col] * att[..., None], row,
                                  num_segments=n)
        if l < last:
            h = out.reshape(n, heads * dh) + p["b"]
            h, key = common.hidden_activation(h, p, key, model["dropout"])
        else:
            h = out.mean(axis=1) + p["b"]
    return h


def loss(model: dict, params, batch, dtype=common.F32, key=None):
    logits = forward(model, params, batch, dtype, key)
    return common.cross_entropy(logits[batch["out"]], batch["labels"])


def forward_flops(model: dict, n: int, e: int) -> int:
    """Per layer with input width ``d_in`` and ``H`` heads of width ``d``,
    ``w = H·d``: ``2·n·d_in·w`` for the transform, ``4·n·w`` for the two
    attention products with ``a_src`` and ``a_dst``, and ``2·e·w`` for the
    attention-weighted sum over edges. The output layer's ``w`` is heads
    times classes: it averages its heads after the sum."""
    dims = common.dims(model)
    total = 0
    for l in range(model["num_layers"]):
        d_in, w = dims[l], dims[l + 1]
        if l == model["num_layers"] - 1:
            w = model["heads"] * w
        total += 2 * n * d_in * w + 4 * n * w + 2 * e * w
    return int(total)
