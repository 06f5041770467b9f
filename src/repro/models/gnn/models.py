"""The paper's three evaluation GNNs: GCN, GAT, GraphSAGE (App. B configs).

Pure-JAX functional models: params are pytrees, apply is jit/pjit-safe, all
shapes static. Batch format = padded induced subgraph (core.batches).
All models use LayerNorm, ReLU and dropout per paper App. B.

Device ops carry ``jax.named_scope`` paths: ``layer{l}`` around each layer
and, inside it, ``transform`` (dense projections and bias), ``attention``
(GAT's edge logits and softmax), ``aggregate`` (the neighbour sum on any
backend) and ``norm`` (LayerNorm). Backward ops keep them inside
``transpose(jvp(...))``. Scopes change op names only, never the
computation.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import ops


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    kind: str = "gcn"            # gcn | gat | sage
    in_dim: int = 128
    hidden: int = 256            # paper: 256 (ogbn), 512 (Reddit GCN)
    out_dim: int = 40
    num_layers: int = 3          # paper: 3 (ogbn), 2 (Reddit)
    heads: int = 4               # GAT
    dropout: float = 0.3
    dtype: str = "float32"
    # aggregation backend of this executable: segment | bcsr | dense
    # (DESIGN.md §7). Per-batch dispatch is a policy, not a value here:
    # repro.models.gnn.policy sets this field per decision (batch_config)
    # and checks that bcsr batches carry tiles (DESIGN.md §14).
    backend: str = "segment"
    # tuned bcsr feature-tile width (0 = 128-lane default); plan-serving
    # consumers set this per batch from the autotuner's stored decision via
    # repro.models.gnn.policy.batch_config (DESIGN.md §14)
    bcsr_block_f: int = 0


def _glorot(key, shape, dtype):
    fan_in, fan_out = shape[0], shape[-1]
    lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def init_gnn(cfg: GNNConfig, key) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    dims = [cfg.in_dim] + [cfg.hidden] * (cfg.num_layers - 1) + [cfg.out_dim]
    params: Dict = {"layers": []}
    for l in range(cfg.num_layers):
        key, *ks = jax.random.split(key, 6)
        d_in, d_out = dims[l], dims[l + 1]
        if cfg.kind == "gcn":
            layer = {
                "w": _glorot(ks[0], (d_in, d_out), dtype),
                "b": jnp.zeros((d_out,), dtype),
            }
        elif cfg.kind == "sage":
            layer = {
                "w_self": _glorot(ks[0], (d_in, d_out), dtype),
                "w_nbr": _glorot(ks[1], (d_in, d_out), dtype),
                "b": jnp.zeros((d_out,), dtype),
            }
        elif cfg.kind == "gat":
            h = cfg.heads
            dh = d_out // h if l < cfg.num_layers - 1 else d_out
            layer = {
                "w": _glorot(ks[0], (d_in, h * dh), dtype),
                "a_src": _glorot(ks[1], (h, dh), dtype),
                "a_dst": _glorot(ks[2], (h, dh), dtype),
                "b": jnp.zeros((h * dh if l < cfg.num_layers - 1 else d_out,), dtype),
            }
        else:
            raise ValueError(cfg.kind)
        if l < cfg.num_layers - 1:
            layer["ln_scale"] = jnp.ones((d_out,), dtype)
            layer["ln_bias"] = jnp.zeros((d_out,), dtype)
        params["layers"].append(layer)
    return params


def _gcn_layer(p, h, batch, backend="segment", block_f=0):
    with jax.named_scope("transform"):
        h = h @ p["w"]
    h = ops.weighted_agg_backend(h, batch, backend, block_f=block_f)
    with jax.named_scope("transform"):
        return h + p["b"]


def _sage_layer(p, h, batch, backend="segment", block_f=0):
    nbr = ops.mean_agg_backend(h, batch, backend, block_f=block_f)
    with jax.named_scope("transform"):
        return h @ p["w_self"] + nbr @ p["w_nbr"] + p["b"]


def _gat_layer(p, h, batch, backend="segment", block_f=0):
    # GAT recomputes edge weights from attention every step, so there are no
    # precomputable tiles — it always falls back to the segment path
    # (DESIGN.md §7); `backend` is accepted for a uniform layer signature.
    n = h.shape[0]
    heads, dh = p["a_src"].shape
    with jax.named_scope("transform"):
        zf = h @ p["w"]                          # (N, H·dh)
        z = zf.reshape(n, heads, dh)
    src, dst, mask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    with jax.named_scope("attention"):
        e_src = (z * p["a_src"][None]).sum(-1)   # (N, H)
        e_dst = (z * p["a_dst"][None]).sum(-1)
        logits = jax.nn.leaky_relu(e_src[src] + e_dst[dst], 0.2)   # (E, H)
        att = ops.segment_softmax(logits, src, n, mask)
    # Each edge scatters one flat row, never a (H, dh) slab: a TPU
    # scatter-add costs per (sublane, 128-lane) tile an update spans, and a
    # slab takes a tile row per head (DESIGN.md §7).
    with jax.named_scope("aggregate"):
        if p["b"].shape[0] == heads * dh:   # hidden layers: concat heads
            msgs = zf[dst] * jnp.repeat(att, dh, axis=1)        # (E, H·dh)
        else:                               # output layer: average heads
            msgs = (z[dst] * att[..., None]).mean(axis=1)       # (E, C)
        out = jax.ops.segment_sum(msgs, src, num_segments=n)
    with jax.named_scope("transform"):
        return out + p["b"]


_LAYERS = {"gcn": _gcn_layer, "sage": _sage_layer, "gat": _gat_layer}


def gnn_apply(cfg: GNNConfig, params: Dict, batch: Dict[str, jnp.ndarray],
              rng: Optional[jax.Array] = None, train: bool = False) -> jnp.ndarray:
    """Forward pass on one padded batch. Returns logits for ALL nodes (N, C);
    the caller selects output rows via batch['output_idx']."""
    layer_fn = _LAYERS[cfg.kind]
    h = batch["features"].astype(jnp.dtype(cfg.dtype))
    if "edge_mask" not in batch:
        batch = dict(batch)
        batch["edge_mask"] = (batch["edge_weight"] != 0).astype(h.dtype)
    backend = ops.check_backend(getattr(cfg, "backend", "segment"))
    block_f = int(getattr(cfg, "bcsr_block_f", 0))
    for l, p in enumerate(params["layers"]):
        with jax.named_scope(f"layer{l}"):
            h = layer_fn(p, h, batch, backend, block_f)
            if l < cfg.num_layers - 1:
                h = ops.layer_norm(h, p["ln_scale"], p["ln_bias"])
                h = jax.nn.relu(h)
                if train and rng is not None:
                    rng, sub = jax.random.split(rng)
                    h = ops.dropout(h, cfg.dropout, sub, deterministic=False)
    return h


def output_logits(logits_all: jnp.ndarray, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Select the batch's output-node rows (paper: only output nodes get
    predictions; auxiliary nodes exist only to feed them)."""
    return logits_all[batch["output_idx"]]


def masked_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                mask: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def masked_accuracy(logits: jnp.ndarray, labels: jnp.ndarray,
                    mask: jnp.ndarray) -> jnp.ndarray:
    pred = logits.argmax(-1)
    correct = (pred == labels).astype(jnp.float32) * mask
    return correct.sum() / jnp.maximum(mask.sum(), 1.0)
