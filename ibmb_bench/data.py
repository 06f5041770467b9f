"""A cell's data: the graph, the program's dataset and plans, the weights,
and the reference's batches.

The graph comes from the configuration's own generator parameters and seed;
the dataset and each plan are cached under ``ibmb_bench/.cache/<config>/``,
the graph by a digest of its parameters and a plan by the pipeline's own
fingerprint, so only a cell's first run in a checkout builds them.
"""
from __future__ import annotations

import os

import numpy as np

from ibmb_bench import graphgen, reference
from ibmb_bench.reference import graph as ref_graph


def graph(ctx) -> graphgen.GraphData:
    spec = graphgen.graph_spec(ctx.config)
    path = ctx.cache_path(f"graph-{graphgen.spec_digest(spec)}.npz")
    if os.path.exists(path):
        return graphgen.load(path)
    data = graphgen.generate(spec)
    graphgen.save(data, path)
    return data


def program_dataset(ctx, g: graphgen.GraphData):
    """The program's dataset object, built by its own preprocessing:
    undirected unit-weight graph, and the GCN-normalised one with self
    loops."""
    from repro.graph.csr import coo_to_csr, gcn_preprocess, make_undirected
    from repro.graph.datasets import GraphDataset
    raw = make_undirected(coo_to_csr(g.src, g.dst, g.num_nodes))
    return GraphDataset(ctx.config["name"], raw, gcn_preprocess(raw),
                        g.features, g.labels, dict(g.splits))


def ibmb_config(ctx):
    from repro.core import IBMBConfig
    return IBMBConfig(**ctx.config["ibmb"])


def model_config(ctx):
    """The program's model of the configuration's every ``model`` field; a
    field the program lacks raises a ``TypeError`` that names it."""
    from repro.models.gnn.models import GNNConfig
    return GNNConfig(**ctx.model)


def policy(ctx):
    from repro.models.gnn.policy import BackendPolicy
    name = ctx.config["policy"]
    return BackendPolicy.auto() if name == "auto" else \
        BackendPolicy.fixed(name)


def plan(ctx, dataset, split: str, for_inference: bool):
    from repro.core import IBMBPipeline
    from repro.core.plan import Plan
    pipe = IBMBPipeline(dataset, ibmb_config(ctx))
    fp = pipe.fingerprint(split, for_inference)
    path = ctx.cache_path(f"plan-{split}-{fp}.npz")
    if os.path.exists(path):
        return Plan.load(path, expect_fingerprint=fp)
    p = pipe.plan(split, for_inference=for_inference)
    p.save(path)
    ctx.log(f"built the {split} plan: {len(p)} batches, "
            f"{p.cache.nbytes() / 2**30:.3f} GiB, timings {p.timings}")
    return p


def check_model(ctx, dataset) -> None:
    m = ctx.model
    if (m["in_dim"], m["out_dim"]) != (dataset.feat_dim,
                                       dataset.num_classes):
        raise ValueError(f"model widths {m['in_dim']}->{m['out_dim']} do "
                         f"not fit the graph's {dataset.feat_dim} features "
                         f"and {dataset.num_classes} classes")


def batch_membership(plan, b: int):
    """Batch ``b`` of a plan as the reference reads it: its global node ids
    in local order (-1 pads) and the global ids of its outputs."""
    ids = np.asarray(plan.node_ids[b])
    fields = plan.cache.fields
    out_local = np.asarray(fields["output_idx"][b])[
        np.asarray(fields["output_mask"][b]) > 0]
    return ids, ids[out_local]


def reference_batch(g: graphgen.GraphData, ng: ref_graph.NormGraph, plan,
                    b: int):
    ids, outs = batch_membership(plan, b)
    return ref_graph.build_batch(ng, g.features, g.labels, ids, outs)


def device_weights(model: dict, key, random_affine: bool):
    """Weights of the model's reference, made on the device in one jitted
    call from ``key``."""
    import jax
    ref = reference.for_model(model)
    return jax.jit(lambda k: ref.init_params(model, k, random_affine))(key)
