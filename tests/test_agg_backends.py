"""Aggregation-backend subsystem (DESIGN.md §7): backend equivalence on every
GNN variant, BCSR conversion correctness, node-reordering tile-fill
regression, and end-to-end segment-vs-bcsr training parity."""
import jax
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import IBMBPipeline, IBMBConfig
from repro.core.batches import batch_node_order, build_batches
from repro.kernels.spmm.ops import csr_to_bcsr, spmm_bcsr, spmm_bcsr_sym
from repro.models.gnn import GNNConfig, init_gnn, gnn_apply


@pytest.fixture(scope="module")
def bcsr_batches(tiny_ds):
    pipe = IBMBPipeline(tiny_ds, IBMBConfig(
        variant="node", k_per_output=8, max_outputs_per_batch=64,
        pad_multiple=32, backend="bcsr"))
    return pipe.preprocess("train")


# ------------------------------------------------------- backend equivalence
@pytest.mark.parametrize("kind", ["gcn", "sage", "gat"])
@pytest.mark.parametrize("backend", ["bcsr", "dense"])
def test_backend_matches_segment_reference(tiny_ds, bcsr_batches, kind, backend):
    """bcsr (interpret-mode Pallas) and dense match the segment reference on
    every GNN variant, on real padded/masked-edge batches."""
    b = bcsr_batches[0]
    assert b.has_bcsr
    # the batch genuinely exercises padding + masked edges
    assert not b.node_mask.all() and not b.edge_mask.all()
    bd = b.device_arrays()
    outs = {}
    for be in ["segment", backend]:
        cfg = GNNConfig(kind=kind, in_dim=tiny_ds.feat_dim, hidden=64,
                        out_dim=tiny_ds.num_classes, num_layers=3, backend=be)
        params = init_gnn(cfg, jax.random.PRNGKey(0))
        outs[be] = np.asarray(gnn_apply(cfg, params, bd))
    np.testing.assert_allclose(outs[backend], outs["segment"], atol=1e-4)


@pytest.mark.parametrize("kind", ["gcn", "sage"])
def test_backend_gradient_matches_segment(tiny_ds, bcsr_batches, kind):
    """The custom-vjp symmetric SpMM gives the same parameter gradients as
    the differentiable segment path (DESIGN.md §7)."""
    from repro.models.gnn.models import output_logits, masked_xent
    bd = bcsr_batches[0].device_arrays()

    grads = {}
    for be in ["segment", "bcsr"]:
        cfg = GNNConfig(kind=kind, in_dim=tiny_ds.feat_dim, hidden=32,
                        out_dim=tiny_ds.num_classes, num_layers=2, backend=be)
        params = init_gnn(cfg, jax.random.PRNGKey(1))

        def loss(p):
            h = gnn_apply(cfg, p, bd)
            return masked_xent(output_logits(h, bd), bd["labels"],
                               bd["output_mask"])

        grads[be] = jax.grad(loss)(params)
    for ga, gb in zip(jax.tree_util.tree_leaves(grads["segment"]),
                      jax.tree_util.tree_leaves(grads["bcsr"])):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), atol=1e-4)


@pytest.mark.parametrize("width", [40, 47])
def test_fused_padded_lanes_match_segment(bcsr_batches, monkeypatch, width):
    """A feature width that is not a multiple of 128 (a class-count output
    layer) runs the fused kernel on zero-padded lanes: forward and gradient
    match the segment reference. Steered to the interpret-mode kernel, as
    ``_spmm_tiles`` would pick the compiled one on TPU."""
    from repro.models.gnn import ops as gnn_ops
    monkeypatch.setattr(gnn_ops, "_spmm_impl", lambda: "fused_interpret")
    bd = bcsr_batches[0].device_arrays()
    n = bd["features"].shape[0]
    h = np.random.default_rng(width).normal(size=(n, width)).astype(np.float32)
    g = np.random.default_rng(width + 1).normal(
        size=(n, width)).astype(np.float32)
    outs = {}
    for be in ("segment", "bcsr"):
        agg = lambda x: gnn_ops.weighted_agg_backend(x, bd, be)
        out, vjp = jax.vjp(agg, h)
        outs[be] = (np.asarray(out), np.asarray(vjp(g)[0]))
    assert outs["bcsr"][0].shape == (n, width)
    for got, want in zip(outs["bcsr"], outs["segment"]):
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_bcsr_backend_requires_tiles(tiny_ds):
    pipe = IBMBPipeline(tiny_ds, IBMBConfig(
        variant="node", k_per_output=8, max_outputs_per_batch=64,
        pad_multiple=32))                       # segment pipeline: no tiles
    bd = pipe.preprocess("train")[0].device_arrays()
    assert "tile_cols" not in bd
    cfg = GNNConfig(kind="gcn", in_dim=tiny_ds.feat_dim, hidden=32,
                    out_dim=tiny_ds.num_classes, num_layers=2, backend="bcsr")
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="bcsr"):
        gnn_apply(cfg, params, bd)


@pytest.mark.parametrize("name", ["auto", "warp"])
def test_forward_refuses_a_name_outside_backends(tiny_ds, bcsr_batches,
                                                 name):
    # a raw forward runs the backend it is given and refuses any other name
    # rather than falling through to segment; per-batch dispatch is a policy
    cfg = GNNConfig(kind="gcn", in_dim=tiny_ds.feat_dim, hidden=32,
                    out_dim=tiny_ds.num_classes, num_layers=2, backend=name)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    bd = bcsr_batches[0].device_arrays()
    with pytest.raises(ValueError, match=r"BackendPolicy\.auto\(\)"):
        gnn_apply(cfg, params, bd)


# ------------------------------------------------------------ conversion
@pytest.mark.parametrize("n,nc,density,block", [
    (300, 300, 0.02, 128), (130, 200, 0.1, 64), (64, 64, 0.3, 32)])
def test_csr_to_bcsr_dense_reconstruction(n, nc, density, block):
    """Vectorized conversion reproduces the matrix exactly (tile scatter)."""
    m = sp.random(n, nc, density=density, random_state=7, format="csr",
                  dtype=np.float32)
    bc = csr_to_bcsr(m.indptr, m.indices, m.data, n, nc, block=block)
    dense = np.zeros((bc.num_rows, bc.num_cols), np.float32)
    r_t, k_t, b, _ = bc.tile_vals.shape
    for r in range(r_t):
        for k in range(k_t):
            c = int(bc.tile_cols[r, k])
            dense[r * b:(r + 1) * b, c * b:(c + 1) * b] += bc.tile_vals[r, k]
    want = np.zeros_like(dense)
    want[:n, :nc] = m.toarray()
    np.testing.assert_array_equal(dense, want)


def test_csr_to_bcsr_pad_k_and_empty():
    bc = csr_to_bcsr(np.zeros(9, np.int64), np.zeros(0, np.int32),
                     np.zeros(0, np.float32), 8, 8, block=8, pad_k=4)
    assert bc.tile_vals.shape == (1, 4, 8, 8)
    assert bc.density_stats()["nonzero_tiles"] == 0
    m = sp.random(64, 64, density=0.1, random_state=0, format="csr",
                  dtype=np.float32)
    tight = csr_to_bcsr(m.indptr, m.indices, m.data, 64, 64, block=32)
    k = tight.tile_cols.shape[1]
    padded = csr_to_bcsr(m.indptr, m.indices, m.data, 64, 64, block=32,
                         pad_k=k + 3)
    assert padded.tile_cols.shape[1] == k + 3
    x = np.random.default_rng(0).normal(size=(64, 16)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(spmm_bcsr(tight.tile_cols, tight.tile_vals, x)),
        np.asarray(spmm_bcsr(padded.tile_cols, padded.tile_vals, x)),
        atol=1e-5)
    with pytest.raises(ValueError):
        csr_to_bcsr(m.indptr, m.indices, m.data, 64, 64, block=32, pad_k=1)


def test_spmm_sym_vjp_is_transpose():
    """For symmetric A, d(A@x)/dx applied to g must equal A@g."""
    rng = np.random.default_rng(3)
    a = sp.random(96, 96, density=0.1, random_state=3, format="csr",
                  dtype=np.float32)
    a = (a + a.T).tocsr()
    bc = csr_to_bcsr(a.indptr, a.indices, a.data, 96, 96, block=32)
    x = rng.normal(size=(96, 8)).astype(np.float32)
    g = rng.normal(size=(96, 8)).astype(np.float32)
    _, vjp = jax.vjp(lambda x_: spmm_bcsr_sym(bc.tile_cols, bc.tile_vals, x_),
                     x)
    (dx,) = vjp(g)
    np.testing.assert_allclose(np.asarray(dx), a.T @ g, atol=1e-4)


# ------------------------------------------------- reordering / tile fill
def _shuffled_band_graph(n=256, width=3, seed=0):
    """Banded (locality-rich) graph whose node ids are shuffled, so
    sorted-global-id order scatters nonzeros across tiles."""
    from repro.graph.csr import coo_to_csr, make_undirected
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    src, dst = [], []
    for d in range(1, width + 1):
        src.append(perm[:-d]); dst.append(perm[d:])
    g = coo_to_csr(np.concatenate(src), np.concatenate(dst), n)
    return make_undirected(g)


def test_reorder_concentrates_tiles():
    """Tile-fill regression (DESIGN.md §7): BFS/RCM reordering must populate
    no more tiles than the identity order, and strictly fewer on a
    shuffled banded graph, with higher per-tile fill."""
    g = _shuffled_band_graph()
    n = g.num_nodes
    feats = np.zeros((n, 4), np.float32)
    labels = np.zeros(n, np.int32)
    outs = [np.arange(n)]
    stats = {}
    for mode in ["none", "bfs"]:
        (b,) = build_batches(g, feats, labels, outs, outs, pad_multiple=64,
                             bcsr_block=64, reorder=mode)
        stats[mode] = b.bcsr_stats()
    assert stats["bfs"]["nonzero_tiles"] < stats["none"]["nonzero_tiles"], stats
    assert stats["bfs"]["tile_fill"] > stats["none"]["tile_fill"], stats


def test_reordered_batches_stay_equivalent(tiny_ds):
    """Reordering permutes local indices consistently: the segment backend
    gives identical output logits on reordered vs unordered batches."""
    cfgs = dict(variant="node", k_per_output=8, max_outputs_per_batch=64,
                pad_multiple=32)
    plain = IBMBPipeline(tiny_ds, IBMBConfig(**cfgs)).preprocess("train")
    tiled = IBMBPipeline(tiny_ds, IBMBConfig(**cfgs, backend="bcsr")).preprocess("train")
    cfg = GNNConfig(kind="gcn", in_dim=tiny_ds.feat_dim, hidden=32,
                    out_dim=tiny_ds.num_classes, num_layers=2)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    from repro.models.gnn.models import output_logits
    for bp, bt in zip(plain, tiled):
        # same outputs in the same order → same REAL logits rows (padded
        # output slots point at local node 0, which reordering relabels)
        dp, dt = bp.device_arrays(), bt.device_arrays()
        lp = np.asarray(output_logits(gnn_apply(cfg, params, dp), dp))
        lt = np.asarray(output_logits(gnn_apply(cfg, params, dt), dt))
        m = bp.output_mask
        assert np.array_equal(m, bt.output_mask)
        np.testing.assert_allclose(lp[m], lt[m], atol=1e-5)


def test_batch_node_order_modes():
    g = _shuffled_band_graph(n=64, width=2)
    src, dst = g.to_coo()
    for mode in ["none", "bfs", "degree"]:
        perm = batch_node_order(64, src, dst, mode=mode)
        assert sorted(perm.tolist()) == list(range(64))
    with pytest.raises(ValueError):
        batch_node_order(64, src, dst, mode="zigzag")


def _rcm_reference(n, src, dst):
    """Reverse Cuthill-McKee with Python's sort: seeds and each node's new
    neighbours ranked by (degree, node id)."""
    nbrs = [set() for _ in range(n)]
    for u, v in zip(src.tolist(), dst.tolist()):
        nbrs[u].add(v)
        nbrs[v].add(u)
    deg = [len(s) for s in nbrs]
    seen, order = set(), []
    for s in sorted(range(n), key=lambda v: (deg[v], v)):
        if s in seen:
            continue
        seen.add(s)
        order.append(s)
        head = len(order) - 1
        while head < len(order):
            new = sorted((v for v in nbrs[order[head]] if v not in seen),
                         key=lambda v: (deg[v], v))
            seen.update(new)
            order.extend(new)
            head += 1
    return order[::-1]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfs_order_breaks_degree_ties_by_id(seed):
    """The "bfs" order is a function of the graph alone: every degree tie
    breaks by node id, never by how the CPU's SIMD sort orders equal keys
    (which made plans, and their tile count K, differ between hosts)."""
    rng = np.random.default_rng(seed)
    n = 300
    src = rng.integers(0, n, 600)
    dst = rng.integers(0, n, 600)
    keep = src != dst            # no self loops; some nodes stay isolated
    perm = batch_node_order(n, src[keep], dst[keep], mode="bfs")
    assert perm.tolist() == _rcm_reference(n, src[keep], dst[keep])


def test_asymmetric_adjacency_rejected():
    """bcsr emission refuses directed batch adjacencies — the backward pass
    would silently use Aᵀ ≠ A (DESIGN.md §7)."""
    from repro.graph.csr import coo_to_csr
    n = 40
    rng = np.random.default_rng(0)
    g = coo_to_csr(rng.integers(0, n, 200), rng.integers(0, n, 200), n)
    feats = np.zeros((n, 4), np.float32)
    labels = np.zeros(n, np.int32)
    with pytest.raises(ValueError, match="symmetric"):
        build_batches(g, feats, labels, [np.arange(n)], [np.arange(n)],
                      pad_multiple=32, bcsr_block=32, reorder="none")


# ------------------------------------------------------------- end-to-end
def test_bcsr_trains_end_to_end_matching_segment(tiny_ds):
    """Acceptance: GNNConfig(backend='bcsr') trains/evals through
    IBMBPipeline + GNNTrainer with loss/acc matching segment within 1e-4."""
    from repro.train import GNNTrainer
    pipe = IBMBPipeline(tiny_ds, IBMBConfig(
        variant="node", k_per_output=8, max_outputs_per_batch=64,
        pad_multiple=32, backend="bcsr"))
    tr = pipe.preprocess("train")
    va = pipe.preprocess("val", for_inference=True)
    hist = {}
    for be in ["segment", "bcsr"]:
        cfg = GNNConfig(kind="gcn", in_dim=tiny_ds.feat_dim, hidden=32,
                        out_dim=tiny_ds.num_classes, num_layers=2,
                        dropout=0.0, backend=be)
        res = GNNTrainer(cfg, lr=1e-3, seed=0).fit(
            tr, va, tiny_ds.num_classes, epochs=3, schedule_mode="none")
        hist[be] = res.history
    for hs, hb in zip(hist["segment"], hist["bcsr"]):
        assert abs(hs["train_loss"] - hb["train_loss"]) < 1e-4
        assert abs(hs["val_loss"] - hb["val_loss"]) < 1e-4
        assert abs(hs["val_acc"] - hb["val_acc"]) < 1e-4
