"""Message-passing primitives on padded COO edge lists + backend dispatch.

All ops take static-shape padded arrays (see core.batches.PaddedBatch) —
padded edges carry weight 0 and point at node 0, so weighted segment sums are
exact without branching. This is the TPU-friendly formulation: gathers +
segment reductions lower to XLA gather/scatter-add which the SPMD partitioner
understands.

Aggregation runs on one of three backends (DESIGN.md §7):

* "segment" — COO gather + ``segment_sum`` (reference; XLA scatter-add).
* "bcsr"    — the Pallas block-CSR SpMM over the tiles that preprocessing
              emitted (``core.batches.build_batches(bcsr_block=...)``):
              the fused Pallas kernel on TPU, the compiled streaming SpMM
              elsewhere.
* "dense"   — materialize the (N, N) batch adjacency and matmul; the
              MXU-roofline upper bound the tiled kernel is judged against.

Which backend a batch runs is decided in one place,
``repro.models.gnn.policy`` (DESIGN.md §14): ``BackendPolicy`` maps each
batch to a backend (fixed, or the plan's autotuned decisions) and checks
that a bcsr batch carries tiles. These primitives run the backend they are
given; :func:`check_backend` refuses any other name. GAT always uses the segment path (its edge weights are recomputed
by attention every step, so there are no precomputable tiles).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BACKENDS = ("segment", "bcsr", "dense")


def check_backend(name: str) -> str:
    """`name` if it is one of :data:`BACKENDS`; a ``ValueError`` otherwise.
    Per-batch dispatch is a policy, never a backend name."""
    if name not in BACKENDS:
        raise ValueError(
            f"unknown aggregation backend {name!r}; want one of {BACKENDS} "
            f"— for per-batch dispatch pass backend=BackendPolicy.auto() to "
            f"the trainer, engine or executor (DESIGN.md §14)")
    return name


def _require_tiles(batch) -> None:
    if "tile_cols" not in batch or "tile_vals" not in batch:
        raise ValueError(
            "backend='bcsr' needs tile_cols/tile_vals in the batch — build "
            "batches with bcsr_block set (IBMBConfig(backend='bcsr') or "
            "build_batches(bcsr_block=128)), or use backend='segment'")


def _spmm_impl() -> str:
    """SpMM impl for this process's backend: the fused gather+SpMM Pallas
    kernel on TPU, the compiled streaming path everywhere else."""
    return "fused" if jax.default_backend() == "tpu" else "stream"


def _spmm_tiles(tile_cols: jnp.ndarray, tile_vals: jnp.ndarray,
                x: jnp.ndarray, block_f: int = 0) -> jnp.ndarray:
    """A @ x through the symmetric-adjacency SpMM (DESIGN.md §7/§14).

    ``block_f`` is the autotuner's tuned feature-tile width; 0 (or a width
    that does not divide the live feature dim — hidden dims vary per layer)
    falls back to the 128-lane default. The fused kernel zero-pads a width
    that is not a multiple of 128 (e.g. a class-count output layer) and
    slices the result back.
    """
    from repro.kernels.spmm.ops import spmm_bcsr_sym
    r, _, b, _ = tile_vals.shape
    assert r * b == x.shape[0], (
        f"bcsr tiles cover {r * b} rows but h has {x.shape[0]}")
    f = x.shape[1]
    bf = int(block_f) if block_f and f % block_f == 0 else 128
    return spmm_bcsr_sym(tile_cols, tile_vals, x, _spmm_impl(), bf)


def _dense_adj(n: int, edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
               values: jnp.ndarray, dtype) -> jnp.ndarray:
    return jnp.zeros((n, n), dtype).at[edge_src, edge_dst].add(
        values.astype(dtype))


def weighted_agg(h: jnp.ndarray, edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                 edge_weight: jnp.ndarray) -> jnp.ndarray:
    """out[u] = Σ_{(u,v)∈E} w_uv · h[v]   (rows = edge_src, gathers edge_dst).

    h: (N, F); edges are local indices; padded edges have weight 0.
    """
    msgs = h[edge_dst] * edge_weight[:, None]
    return jax.ops.segment_sum(msgs, edge_src, num_segments=h.shape[0])


def mean_agg(h: jnp.ndarray, edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
             edge_mask: jnp.ndarray) -> jnp.ndarray:
    """Mean aggregation (GraphSAGE): masked mean over real edges."""
    w = edge_mask.astype(h.dtype)
    s = jax.ops.segment_sum(h[edge_dst] * w[:, None], edge_src,
                            num_segments=h.shape[0])
    cnt = jax.ops.segment_sum(w, edge_src, num_segments=h.shape[0])
    return s / jnp.maximum(cnt, 1.0)[:, None]


def weighted_agg_backend(h: jnp.ndarray, batch, backend: str = "segment",
                         block_f: int = 0) -> jnp.ndarray:
    """``out[u] = Σ w_uv h[v]`` on the selected backend (DESIGN.md §7).

    All three backends compute the identical weighted sum — the
    backend-equivalence test suite pins them to the segment reference.
    ``block_f`` is the tuned bcsr feature-tile width (DESIGN.md §14).
    """
    with jax.named_scope("aggregate"):
        if backend == "bcsr":
            _require_tiles(batch)
            return _spmm_tiles(batch["tile_cols"], batch["tile_vals"], h,
                               block_f=block_f)
        if backend == "dense":
            a = _dense_adj(h.shape[0], batch["edge_src"], batch["edge_dst"],
                           batch["edge_weight"], h.dtype)
            return a @ h
        return weighted_agg(h, batch["edge_src"], batch["edge_dst"],
                            batch["edge_weight"])


def mean_agg_backend(h: jnp.ndarray, batch, backend: str = "segment",
                     block_f: int = 0) -> jnp.ndarray:
    """Masked neighbor mean on the selected backend (DESIGN.md §7).

    bcsr/dense recover the binary adjacency from nonzero weights: the batch
    graph is GCN-normalized, so every real edge has a strictly positive
    weight and ``w != 0`` equals the edge mask.
    """
    with jax.named_scope("aggregate"):
        if backend == "bcsr":
            _require_tiles(batch)
            bin_tiles = (batch["tile_vals"] != 0).astype(h.dtype)
            s = _spmm_tiles(batch["tile_cols"], bin_tiles, h, block_f=block_f)
            cnt = bin_tiles.sum(axis=(1, 3)).reshape(-1)   # (R·B,) real in-batch degree
            return s / jnp.maximum(cnt, 1.0)[:, None]
        if backend == "dense":
            a = _dense_adj(h.shape[0], batch["edge_src"], batch["edge_dst"],
                           (batch["edge_weight"] != 0), h.dtype)
            return (a @ h) / jnp.maximum(a.sum(axis=1), 1.0)[:, None]
        return mean_agg(h, batch["edge_src"], batch["edge_dst"],
                        batch["edge_mask"])


def segment_softmax(logits: jnp.ndarray, segment_ids: jnp.ndarray,
                    num_segments: int, mask: jnp.ndarray) -> jnp.ndarray:
    """Numerically-stable softmax over edges grouped by destination segment.

    logits: (E, H); mask: (E,) 1.0 for real edges.
    """
    neg = jnp.asarray(-1e9, logits.dtype)
    logits = jnp.where(mask[:, None] > 0, logits, neg)
    seg_max = jax.ops.segment_max(logits, segment_ids, num_segments=num_segments)
    logits = logits - seg_max[segment_ids]
    ex = jnp.exp(logits) * mask[:, None]
    denom = jax.ops.segment_sum(ex, segment_ids, num_segments=num_segments)
    return ex / jnp.maximum(denom[segment_ids], 1e-16)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    with jax.named_scope("norm"):
        mu = x.mean(axis=-1, keepdims=True)
        var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def dropout(x: jnp.ndarray, rate: float, key, deterministic: bool) -> jnp.ndarray:
    if deterministic or rate <= 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)
