"""GNN models: shapes, finiteness, and the padding-invariance property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import IBMBPipeline, IBMBConfig
from repro.models.gnn import GNNConfig, init_gnn, gnn_apply, ops
from repro.models.gnn.models import output_logits, masked_xent, masked_accuracy


@pytest.fixture(scope="module")
def batch(tiny_ds):
    pipe = IBMBPipeline(tiny_ds, IBMBConfig(
        variant="node", k_per_output=8, max_outputs_per_batch=64,
        pad_multiple=32))
    return pipe.preprocess("train")[0]


@pytest.mark.parametrize("kind", ["gcn", "gat", "sage"])
def test_forward_shapes_finite(tiny_ds, batch, kind):
    cfg = GNNConfig(kind=kind, in_dim=tiny_ds.feat_dim, hidden=64,
                    out_dim=tiny_ds.num_classes, num_layers=3)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    b = batch.device_arrays()
    logits = output_logits(gnn_apply(cfg, params, b), b)
    assert logits.shape == (batch.output_idx.shape[0], tiny_ds.num_classes)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("kind", ["gcn", "gat", "sage"])
def test_padding_invariance(tiny_ds, batch, kind):
    """Doubling the padding must not change real-node outputs — the masked
    formulation is exact, not approximate."""
    cfg = GNNConfig(kind=kind, in_dim=tiny_ds.feat_dim, hidden=32,
                    out_dim=tiny_ds.num_classes, num_layers=2)
    params = init_gnn(cfg, jax.random.PRNGKey(1))
    b = batch.device_arrays()
    out1 = np.asarray(gnn_apply(cfg, params, b))

    # re-pad: append extra zero nodes/edges
    extra_n, extra_e = 32, 64
    b2 = dict(b)
    f = b["features"]
    b2["features"] = np.concatenate(
        [np.asarray(f), np.zeros((extra_n, f.shape[1]), np.float32)])
    b2["node_mask"] = np.concatenate(
        [np.asarray(b["node_mask"]), np.zeros(extra_n, np.float32)])
    b2["edge_src"] = np.concatenate(
        [np.asarray(b["edge_src"]), np.zeros(extra_e, np.int32)])
    b2["edge_dst"] = np.concatenate(
        [np.asarray(b["edge_dst"]), np.zeros(extra_e, np.int32)])
    b2["edge_weight"] = np.concatenate(
        [np.asarray(b["edge_weight"]), np.zeros(extra_e, np.float32)])
    out2 = np.asarray(gnn_apply(cfg, params, b2))
    n = out1.shape[0]
    np.testing.assert_allclose(out1, out2[:n], rtol=1e-5, atol=1e-5)


def test_losses_and_metrics(tiny_ds, batch):
    cfg = GNNConfig(kind="gcn", in_dim=tiny_ds.feat_dim, hidden=32,
                    out_dim=tiny_ds.num_classes, num_layers=2)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    b = batch.device_arrays()
    logits = output_logits(gnn_apply(cfg, params, b), b)
    loss = masked_xent(logits, b["labels"], b["output_mask"])
    acc = masked_accuracy(logits, b["labels"], b["output_mask"])
    assert np.isfinite(float(loss)) and 0 <= float(acc) <= 1
    # loss at init should be close to ln(num_classes)
    assert abs(float(loss) - np.log(tiny_ds.num_classes)) < 1.0


def _gat_batch(n_real, n_pad, e_real, e_pad, in_dim, seed):
    """A random GAT batch with padded nodes and padded, masked edges (weight
    0, pointing at node 0), as ``core.batches`` pads them."""
    rng = np.random.default_rng(seed)
    n = n_real + n_pad
    feats = np.zeros((n, in_dim), np.float32)
    feats[:n_real] = rng.normal(size=(n_real, in_dim))
    pad = np.zeros(e_pad, np.int32)
    return {
        "features": feats,
        "edge_src": np.concatenate(
            [rng.integers(0, n_real, e_real), pad]).astype(np.int32),
        "edge_dst": np.concatenate(
            [rng.integers(0, n_real, e_real), pad]).astype(np.int32),
        "edge_weight": np.concatenate(
            [rng.uniform(0.1, 1.0, e_real), np.zeros(e_pad)]).astype(np.float32),
        "edge_mask": np.concatenate(
            [np.ones(e_real), np.zeros(e_pad)]).astype(np.float32),
    }


def _plain_gat(cfg, params, batch):
    """GAT with the plain ``(E, H, dh)`` message sum, heads concatenated or
    averaged after the scatter."""
    h = batch["features"]
    src, dst, mask = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    for l, p in enumerate(params["layers"]):
        n = h.shape[0]
        heads, dh = p["a_src"].shape
        z = (h @ p["w"]).reshape(n, heads, dh)
        e_src = (z * p["a_src"][None]).sum(-1)
        e_dst = (z * p["a_dst"][None]).sum(-1)
        att = ops.segment_softmax(
            jax.nn.leaky_relu(e_src[src] + e_dst[dst], 0.2), src, n, mask)
        out = jax.ops.segment_sum(z[dst] * att[..., None], src,
                                  num_segments=n)
        if l < cfg.num_layers - 1:
            h = out.reshape(n, heads * dh) + p["b"]
            h = jax.nn.relu(ops.layer_norm(h, p["ln_scale"], p["ln_bias"]))
        else:
            h = out.mean(axis=1) + p["b"]
    return h


# heads x width of the hidden layers, classes (none a multiple of 128 or of
# the head count, except under one head), layers
GAT_SHAPES = [(4, 32, 47, 3), (4, 16, 41, 2), (3, 5, 7, 2), (1, 24, 10, 2)]


@pytest.mark.parametrize("heads,dh,classes,layers", GAT_SHAPES)
def test_gat_message_sum_matches_plain_head_slabs(heads, dh, classes, layers):
    cfg = GNNConfig(kind="gat", in_dim=12, hidden=heads * dh,
                    out_dim=classes, num_layers=layers, heads=heads)
    params = init_gnn(cfg, jax.random.PRNGKey(3))
    b = {k: jnp.asarray(v)
         for k, v in _gat_batch(50, 14, 300, 84, 12, seed=heads * dh).items()}
    got = gnn_apply(cfg, params, b)
    want = _plain_gat(cfg, params, b)
    assert got.shape == want.shape == (64, classes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)

    labels = jnp.arange(64, dtype=jnp.int32) % classes
    out_mask = (jnp.arange(64) < 50).astype(jnp.float32)

    def loss(fwd):
        return lambda p: masked_xent(fwd(cfg, p, b), labels, out_mask)

    g_got = jax.grad(loss(gnn_apply))(params)
    g_want = jax.grad(loss(_plain_gat))(params)
    for (path, a), w in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=1e-4,
                                   atol=1e-6, err_msg=str(path))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("heads,dh,classes,layers", GAT_SHAPES[:2])
def test_gat_scatters_flat_node_rows(heads, dh, classes, layers):
    """Every scatter-add of a GAT forward writes (N, width) node rows: the
    message sum scatters one flat row per edge, never a (heads, width)
    slab, whose tiles cost a TPU scatter a row each."""
    cfg = GNNConfig(kind="gat", in_dim=12, hidden=heads * dh,
                    out_dim=classes, num_layers=layers, heads=heads)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    b = _gat_batch(50, 14, 300, 84, 12, seed=0)
    jaxpr = jax.make_jaxpr(lambda p: gnn_apply(cfg, p, b))(params).jaxpr
    shapes = [e.invars[0].aval.shape for e in _eqns(jaxpr)
              if e.primitive.name == "scatter-add"]
    assert shapes and all(len(s) == 2 and s[0] == 64 for s in shapes), shapes
    # the message sums: hidden layers' concatenated heads, the output's mean
    widths = [s[1] for s in shapes]
    assert widths.count(heads * dh) == layers - 1 and classes in widths
