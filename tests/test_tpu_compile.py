"""The main path's Pallas kernels compile for a TPU v5e.

Nothing runs: each case lowers and compiles for a described (not attached)
``v5e:2x2`` chip, which refuses what interpret mode accepts, such as a DMA
slice not aligned to the 128-lane tiling or more VMEM than a kernel may
use. The topology is described inside a fixture, so collecting this file
loads no TPU library; where it cannot be described, the cases skip.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.gnn_gcn import CONFIG
from repro.kernels.spmm import spmm_bcsr_sym
from repro.models.gnn import init_gnn
from repro.models.gnn import ops as gnn_ops
from repro.train import GNNTrainer

B = 128
# K of the arxiv-like plans built with IBMBConfig(variant="node",
# backend="bcsr") and otherwise the defaults: 42 train, 69 test, 74 val
ARXIV_K = 74
# the fused kernel used to keep a whole (K, B, B) value row resident and
# double-buffered, which ran out of VMEM from K=128 at F=256
OLD_VMEM_K = 160


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile written to the persistent cache cannot be read back
    # without a chip
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tiles(sharding, r, k):
    return (_sds(sharding, (r, k), jnp.int32),
            _sds(sharding, (r, k, B, B), jnp.float32))


@pytest.mark.parametrize("width", [40, 256])
def test_fused_spmm_fwd_bwd_compiles(one_chip, width):
    """Widths that are not a multiple of 128 (the class-count output layer)
    pad their lanes; the backward runs the same kernel on the cotangent."""
    cols, vals = _tiles(one_chip, 8, 8)
    x = _sds(one_chip, (8 * B, width), jnp.float32)

    def loss(c, v, x_):
        return (spmm_bcsr_sym(c, v, x_, "fused", 128) ** 2).sum()

    hlo = jax.jit(jax.grad(loss, argnums=2)).lower(
        cols, vals, x).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("k", [ARXIV_K, OLD_VMEM_K])
def test_fused_spmm_compiles_at_plan_k(one_chip, k):
    cols, vals = _tiles(one_chip, 4, k)
    x = _sds(one_chip, (8 * B, 256), jnp.float32)
    hlo = jax.jit(lambda c, v, x_: spmm_bcsr_sym(c, v, x_, "fused", 256)
                  ).lower(cols, vals, x).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_gcn_eval_step_compiles(one_chip, monkeypatch):
    """The trainer's bcsr eval step for the paper's GCN at the arxiv-like
    val plan's shapes, steered to the fused kernel as on a TPU."""
    monkeypatch.setattr(gnn_ops, "_spmm_impl", lambda: "fused")
    cfg = dataclasses.replace(CONFIG, in_dim=128, out_dim=40)
    params = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_gnn(cfg, jax.random.PRNGKey(0))))
    mn, me, mo = 11008, 102400, 2048
    cols, vals = _tiles(one_chip, mn // B, ARXIV_K)
    batch = dict(
        edge_src=_sds(one_chip, (me,), jnp.int32),
        edge_dst=_sds(one_chip, (me,), jnp.int32),
        edge_weight=_sds(one_chip, (me,), jnp.float32),
        node_mask=_sds(one_chip, (mn,), jnp.float32),
        output_idx=_sds(one_chip, (mo,), jnp.int32),
        output_mask=_sds(one_chip, (mo,), jnp.float32),
        features=_sds(one_chip, (mn, 128), jnp.float32),
        labels=_sds(one_chip, (mo,), jnp.int32),
        tile_cols=cols, tile_vals=vals)
    step = GNNTrainer(cfg, backend="bcsr")._steps_for("bcsr", 0)["eval"]
    hlo = step.lower(params, batch).compile().as_text()
    # one aggregation per layer: 256, 256 and the 40-class output
    assert hlo.count("tpu_custom_call") >= 3
