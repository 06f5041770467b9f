"""The plain float32 references against the program, at a tiny size on the
CPU: the forward (``gnn_apply``) with and without dropout, the loss
(``masked_xent``) and its gradient, on batches of a real plan, and the
initial weights. Every kind that ``reference.kinds()`` finds is tested,
with its module's ``SMALL`` model fitted to the test graph's widths.

Tolerances: both sides run float32 with full-precision products on the CPU
and differ only in summation order (the reference sums over its own edge
list), so logits and the loss agree to about 1e-6 of their scale; 1e-5
leaves tenfold room. Gradients cancel across nodes and amplify that
order's rounding, so they are held to 1e-4 relative L2 per leaf.
"""
import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts the repo on sys.path)

import jax
import jax.numpy as jnp

from ibmb_bench import graphgen, reference
from ibmb_bench.reference import common, graph as ref_graph

LOGIT_TOL = 1e-5
GRAD_TOL = 1e-4

SPEC = dict(num_nodes=400, num_classes=5, avg_degree=6.0, feat_dim=16,
            homophily=0.9, train_frac=0.5, val_frac=0.2, test_frac=0.3,
            noise=1.0, graph_seed=5)


@pytest.fixture(scope="module")
def setup():
    from repro.core import IBMBConfig, IBMBPipeline
    from repro.graph.csr import coo_to_csr, gcn_preprocess, make_undirected
    from repro.graph.datasets import GraphDataset
    g = graphgen.generate(SPEC)
    raw = make_undirected(coo_to_csr(g.src, g.dst, g.num_nodes))
    ds = GraphDataset("tiny", raw, gcn_preprocess(raw), g.features,
                      g.labels, dict(g.splits))
    pipe = IBMBPipeline(ds, IBMBConfig(variant="node",
                                       max_outputs_per_batch=48))
    plan = pipe.plan("train")
    ng = ref_graph.normalized_graph(g.src, g.dst, g.num_nodes)
    return g, plan, ng


KINDS = reference.kinds()


def _ref(kind):
    return reference.for_model({"kind": kind})


def _model(kind):
    return dict(_ref(kind).SMALL, in_dim=SPEC["feat_dim"],
                out_dim=SPEC["num_classes"])


def _program(kind):
    from repro.models.gnn.models import GNNConfig
    return GNNConfig(**_model(kind))


def _pair(setup, b):
    from ibmb_bench import data
    g, plan, ng = setup
    return plan.cache[b], data.reference_batch(g, ng, plan, b)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("train", [False, True])
def test_forward_matches_gnn_apply(setup, kind, train):
    from repro.models.gnn.models import gnn_apply, output_logits
    ref = _ref(kind)
    params = ref.init_params(_model(kind), jax.random.PRNGKey(3),
                             random_affine=True)
    key = common.dropout_key(jax.random.PRNGKey(9), 2, 1) if train else None
    for b in range(len(setup[1])):
        prog_b, ref_b = _pair(setup, b)
        got = output_logits(gnn_apply(_program(kind), params, prog_b,
                                      rng=key, train=train), prog_b)
        got = np.asarray(got)[np.asarray(prog_b["output_mask"]) > 0]
        want = np.asarray(ref.forward(_model(kind), params, ref_b,
                                      key=key))[ref_b["out"]]
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= LOGIT_TOL * scale


@pytest.mark.parametrize("kind", KINDS)
def test_loss_and_gradient_match(setup, kind):
    from repro.models.gnn.models import gnn_apply, masked_xent, output_logits
    ref = _ref(kind)
    params = ref.init_params(_model(kind), jax.random.PRNGKey(4),
                             random_affine=True)
    key = common.dropout_key(jax.random.PRNGKey(1), 0, 0)
    prog_b, ref_b = _pair(setup, 0)
    prog_b = {k: jnp.asarray(v) for k, v in prog_b.items()}

    def prog_loss(p):
        h = gnn_apply(_program(kind), p, prog_b, rng=key, train=True)
        return masked_xent(output_logits(h, prog_b), prog_b["labels"],
                           prog_b["output_mask"])

    lp, gp = jax.value_and_grad(prog_loss)(params)
    lr, gr = jax.value_and_grad(
        lambda p: ref.loss(_model(kind), p, ref_b, key=key))(params)
    assert abs(float(lp) - float(lr)) <= LOGIT_TOL * abs(float(lr))
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        nb = np.linalg.norm(b)
        if nb < 1e-6:        # nought to rounding (a_src under the softmax)
            assert np.linalg.norm(a) < 1e-5
            continue
        assert np.linalg.norm(a - b) / nb <= GRAD_TOL


@pytest.mark.parametrize("kind", KINDS)
def test_program_init_is_mirrored(kind):
    """The training reference draws the program's initial weights from the
    same key (``fit`` initialises from ``fold_in(rng, 0)``)."""
    from repro.models.gnn.models import init_gnn
    key = jax.random.PRNGKey(21)
    want = init_gnn(_program(kind), key)
    got = _ref(kind).init_params(_model(kind), key)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_normalization_matches_gcn_preprocess():
    from repro.graph.csr import coo_to_csr, gcn_preprocess, make_undirected
    g = graphgen.generate(SPEC)
    ng = ref_graph.normalized_graph(g.src, g.dst, g.num_nodes)
    prog = gcn_preprocess(make_undirected(coo_to_csr(g.src, g.dst,
                                                     g.num_nodes)))
    rows = np.repeat(np.arange(g.num_nodes), np.diff(prog.indptr))
    np.testing.assert_array_equal(rows, ng.rows)
    np.testing.assert_array_equal(prog.indices, ng.cols)
    np.testing.assert_allclose(prog.weights, ng.weight, rtol=1e-6)
