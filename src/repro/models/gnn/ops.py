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

Selection: ``repro.models.gnn.policy.BackendPolicy`` — fixed per-plan or
per-batch *auto* from the plan's autotuned decisions (DESIGN.md §14). A
``GNNConfig.backend`` of ``"auto"`` resolves per batch at trace time by
tile presence. ``REPRO_GNN_BACKEND`` is a deprecated alias that warns once
and maps onto a fixed policy. GAT always uses the segment path (its edge
weights are recomputed by attention every step, so there are no
precomputable tiles).
"""
from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp

BACKENDS = ("segment", "bcsr", "dense")

_env_warned = False


def _env_backend() -> str:
    """Deprecated ``REPRO_GNN_BACKEND`` alias — warns ONCE per process and
    keeps the old force-this-backend semantics (it maps onto
    ``BackendPolicy.fixed``, so it also overrides auto dispatch)."""
    global _env_warned
    name = os.environ.get("REPRO_GNN_BACKEND", "")
    if name and not _env_warned:
        warnings.warn(
            "REPRO_GNN_BACKEND is deprecated: pass "
            "backend=BackendPolicy.fixed(...) (or a backend name) to the "
            "trainer/engine/executor instead (DESIGN.md §14)",
            DeprecationWarning, stacklevel=3)
        _env_warned = True
    return name


def resolve_backend(backend: str, allow_auto: bool = False) -> str:
    """Config value, overridable by the deprecated REPRO_GNN_BACKEND alias
    (DESIGN.md §7/§14). Resolved at trace time — one executable per backend.
    ``allow_auto=True`` passes ``"auto"`` through for callers that resolve
    it per batch (``validate_batch_for_backend``)."""
    b = _env_backend() or backend or "segment"
    if allow_auto and b == "auto":
        return b
    if b not in BACKENDS:
        raise ValueError(f"unknown aggregation backend {b!r}; want one of {BACKENDS}")
    return b


def _require_tiles(batch) -> None:
    if "tile_cols" not in batch or "tile_vals" not in batch:
        raise ValueError(
            "backend='bcsr' needs tile_cols/tile_vals in the batch — build "
            "batches with bcsr_block set (IBMBConfig(backend='bcsr') or "
            "build_batches(bcsr_block=128)), or use backend='segment'")


def validate_batch_for_backend(batch, backend: str, kind: str = "gcn") -> str:
    """Fail fast (not mid-trace) if `batch` lacks what `backend` needs.

    The public pre-flight check for anything that stages batches for a jit'd
    forward (``GNNTrainer``, ``GNNInferenceEngine``): resolves the backend
    (env override included), verifies bcsr tiles are present when required,
    and returns the resolved backend name. `kind` is the GNN variant — GAT
    always runs the segment path (DESIGN.md §7), so it needs no tiles.

    ``backend="auto"`` resolves per batch, at trace time, by tile presence
    (batch *keys* are static under jit): tiles ⇒ bcsr, else segment. This is
    the degenerate auto mode for raw ``gnn_apply`` callers; plan-serving
    consumers dispatch on the autotuner's stored per-batch decisions instead
    (DESIGN.md §14).
    """
    b = resolve_backend(backend, allow_auto=True)
    if b == "auto":
        has_tiles = "tile_cols" in batch and "tile_vals" in batch
        b = "bcsr" if (has_tiles and kind != "gat") else "segment"
    if b == "bcsr" and kind != "gat":
        _require_tiles(batch)
    return b


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
