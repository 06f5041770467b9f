"""BackendPolicy + plan-build autotuner (DESIGN.md §14): policy resolution
precedence, the analytic tile autotuner (determinism, fingerprint pinning,
streamed/resident agreement), plan format v3 round-trips (v2 back-compat
included), the fused/streaming bcsr SpMM impls, and bitwise auto-vs-forced
dispatch parity through the engine, the trainer, and the shard_map executor
(a 1-device mesh runs the full machinery everywhere).
"""
import dataclasses
import json

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import IBMBConfig, IBMBPipeline, Plan, autotune
from repro.core.batches import build_batches
from repro.core.plan import BACKEND_CODES, decode_backends, encode_backends
from repro.graph.csr import coo_to_csr, make_undirected
from repro.kernels.spmm import csr_to_bcsr, spmm_bcsr, spmm_bcsr_sym
from repro.models.gnn import GNNConfig, init_gnn
from repro.models.gnn import policy as gnn_policy
from repro.models.gnn.policy import BackendPolicy
from repro.serve import GNNInferenceEngine
from repro.train import GNNTrainer

# decisions pinned for parity tests: kappa huge → every tiled batch decides
# bcsr; kappa 0 → every batch decides segment. tune_block_fs=() keeps the
# stored block_f at 0, so the auto-dispatched executable's config is
# field-for-field the forced one (bitwise parity is then a jit identity).
ALL_BCSR = dict(autotune=True, auto_kappa=1e9, tune_block_fs=())
ALL_SEG = dict(autotune=True, auto_kappa=0.0, tune_block_fs=())


def _pipe(ds, **kw):
    cfg = dict(variant="node", k_per_output=8, max_outputs_per_batch=16,
               pad_multiple=32, backend="bcsr")
    cfg.update(kw)
    return IBMBPipeline(ds, IBMBConfig(**cfg))


def _cfg(ds, **kw):
    kw.setdefault("dropout", 0.0)
    kw.setdefault("kind", "gcn")
    return GNNConfig(in_dim=ds.feat_dim, hidden=32,
                     out_dim=ds.num_classes, num_layers=2, **kw)


def _band_graph(n=256, width=4, seed=0):
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    src = np.concatenate([perm[:-d] for d in range(1, width + 1)])
    dst = np.concatenate([perm[d:] for d in range(1, width + 1)])
    return make_undirected(coo_to_csr(src, dst, n))


# ------------------------------------------------------------ policy API
def test_as_policy_normalization():
    assert gnn_policy.as_policy(None) is None
    p = gnn_policy.as_policy("bcsr")
    assert p == BackendPolicy.fixed("bcsr") and not p.is_auto
    assert gnn_policy.as_policy("auto").is_auto
    pol = BackendPolicy.auto()
    assert gnn_policy.as_policy(pol) is pol
    with pytest.raises(ValueError, match="unknown aggregation backend"):
        gnn_policy.as_policy("warp")
    with pytest.raises(TypeError, match="BackendPolicy"):
        gnn_policy.as_policy(3)


def test_resolve_precedence_and_auto_base():
    cfg = GNNConfig(kind="gcn", in_dim=4, hidden=8, out_dim=2, num_layers=2,
                    backend="segment")
    # no override → the config's own backend, as a fixed policy
    c, p = gnn_policy.resolve(cfg)
    assert c.backend == "segment" and p == BackendPolicy.fixed("segment")
    # explicit arg wins over the config
    c, p = gnn_policy.resolve(cfg, "dense")
    assert c.backend == "dense" and p.backend == "dense"
    # auto resolves the config to the always-executable segment base
    c, p = gnn_policy.resolve(cfg, BackendPolicy.auto())
    assert c.backend == "segment" and p.is_auto


@pytest.mark.parametrize("name", ["auto", "warp"])
def test_config_backend_outside_backends_is_refused(name):
    """Per-batch dispatch is a policy, never a config value: a config that
    names "auto" (or no backend at all) is refused when it is resolved, and
    the message points at the policy."""
    cfg = GNNConfig(kind="gcn", in_dim=4, hidden=8, out_dim=2, num_layers=2,
                    backend=name)
    with pytest.raises(ValueError, match=r"BackendPolicy\.auto\(\)"):
        gnn_policy.resolve(cfg)


def test_batch_config_is_noop_when_matching():
    cfg = GNNConfig(kind="gcn", in_dim=4, hidden=8, out_dim=2, num_layers=2,
                    backend="bcsr")
    assert gnn_policy.batch_config(cfg, "bcsr", 0) is cfg
    c2 = gnn_policy.batch_config(cfg, "segment", 0)
    assert c2.backend == "segment" and cfg.backend == "bcsr"


def test_superstep_decision_uniform_and_mixed():
    d = [("bcsr", 128), ("bcsr", 128), ("segment", 0), ("bcsr", 256)]
    assert gnn_policy.superstep_decision(d, [0, 1]) == ("bcsr", 128)
    # same backend, mixed block_f → keep the backend, drop the tuned width
    assert gnn_policy.superstep_decision(d, [0, 3]) == ("bcsr", 0)
    # mixed backends → the always-executable fallback
    assert gnn_policy.superstep_decision(d, [1, 2]) == ("segment", 0)


# ----------------------------------------------------- analytic autotuner
@pytest.mark.parametrize("block", [16, 32, 64])
def test_tile_shape_stats_matches_converter(block):
    """The analytic (nonzero_tiles, K) must equal what csr_to_bcsr emits —
    including its drop-zero-weights and empty→K=1 conventions."""
    rng = np.random.default_rng(block)
    n = 128
    a = sp.random(n, n, density=0.03, random_state=int(block),
                  format="csr", dtype=np.float32)
    a = (a + a.T).tocsr()
    coo = a.tocoo()
    src, dst, w = coo.row, coo.col, coo.data.copy()
    w[rng.random(len(w)) < 0.2] = 0.0            # padded entries to drop
    tiles, k = autotune.tile_shape_stats(src, dst, w, n, block)
    nz = w != 0
    g = coo_to_csr(src[nz], dst[nz], n, weights=w[nz])
    bc = csr_to_bcsr(g.indptr, g.indices, g.weights, n, n, block=block)
    stats = bc.density_stats()
    assert tiles == stats["nonzero_tiles"]
    assert k == bc.tile_cols.shape[1]
    # empty adjacency: converter emits one zero tile per row, K=1
    assert autotune.tile_shape_stats(src, dst, np.zeros_like(w), n, block) \
        == (0, 1)


def test_tune_block_f_budget():
    # k=1 single-buffers; everything fits a generous budget → widest wins
    assert autotune.tune_block_f(1, 64, (128, 256, 512), 8192) == 512
    # shrink the budget until only the narrowest candidate fits:
    # footprint = 4*(2*64*64 + 5*64*bf) → 192KiB at 128, 352KiB at 256
    assert autotune.tune_block_f(4, 64, (128, 256, 512), 200) == 128
    # nothing fits, or nothing to choose from → 0, the 128-lane default
    assert autotune.tune_block_f(4, 64, (128, 256), 1) == 0
    assert autotune.tune_block_f(4, 64, (), 8192) == 0


@pytest.mark.parametrize("block,k,vmem_kb", [
    (128, 1, 8192), (128, 75, 8192), (128, 4096, 8192), (128, 8, 300),
    (128, 8, 200), (512, 8, 8192), (1024, 8, 8192), (64, 2, 200)])
def test_autotune_agrees_with_kernel_vmem(block, k, vmem_kb):
    """The tuned width and the backend decision both follow the fused
    kernel's own VMEM footprint, which does not grow with K."""
    from repro.kernels.spmm.fused import LANES, fused_vmem_bytes
    budget = vmem_kb * 1024
    assert fused_vmem_bytes(block, LANES, k) == \
        fused_vmem_bytes(block, LANES, max(k, 2)) or k == 1
    cands = (128, 256, 512)
    bf = autotune.tune_block_f(k, block, cands, vmem_kb)
    fits = [c for c in cands if fused_vmem_bytes(block, c, k) <= budget]
    assert bf == (max(fits) if fits else 0)
    s = dict(edges=10**9, block=block, nonzero_tiles=1, max_tiles_per_row=k)
    want = "bcsr" if fused_vmem_bytes(block, LANES, k) <= budget \
        else "segment"
    assert autotune.decide_backend(s, 16.0, vmem_kb) == want


def test_decide_backend_kappa_threshold():
    s = dict(edges=100, block=16, nonzero_tiles=4,    # padded flops 1024
             max_tiles_per_row=2)
    assert autotune.decide_backend(s, 16.0) == "bcsr"     # 1024 <= 1600
    assert autotune.decide_backend(s, 10.0) == "segment"  # 1024 > 1000
    assert autotune.decide_backend(dict(edges=100), 16.0) == "segment"
    # a kernel that cannot fit the VMEM budget never gets the batch
    assert autotune.decide_backend(s, 16.0, vmem_kb=0) == "segment"


def test_retile_matches_direct_build():
    """Resident retiling (build at the default block, retile at the winner)
    must be bitwise what building at the winner directly produces — the
    invariant that keeps streamed and resident tuned plans identical."""
    g = _band_graph()
    n = g.num_nodes
    feats = np.zeros((n, 4), np.float32)
    labels = np.zeros(n, np.int32)
    outs = [np.arange(n // 2), np.arange(n // 2, n)]
    kw = dict(pad_multiple=64, reorder="bfs")
    at128 = build_batches(g, feats, labels, outs, outs, bcsr_block=128, **kw)
    at32 = build_batches(g, feats, labels, outs, outs, bcsr_block=32, **kw)
    pad_k = at32[0].tile_cols.shape[1]
    retiled = autotune.retile_batches(at128, 32, pad_k)
    for a, b in zip(retiled, at32):
        assert np.array_equal(a.tile_cols, b.tile_cols)
        assert np.array_equal(a.tile_vals, b.tile_vals)


def test_retune_picks_cheapest_block_ties_to_larger():
    g = _band_graph()
    n = g.num_nodes
    feats = np.zeros((n, 4), np.float32)
    labels = np.zeros(n, np.int32)
    outs = [np.arange(n)]
    batches = build_batches(g, feats, labels, outs, outs, pad_multiple=64,
                            bcsr_block=128, reorder="bfs")
    cfg = IBMBConfig(variant="node", backend="bcsr", bcsr_block=128,
                     tune_blocks=(16, 32, 64))
    tuned, block = autotune.retune_tile_block(batches, cfg)
    mn = batches[0].node_ids.shape[0]
    costs, _ = autotune.sweep_tile_blocks(
        batches, autotune.tile_block_candidates(cfg, mn))
    assert block == min(costs, key=lambda b: (costs[b], -b))
    assert tuned[0].tile_vals.shape[-1] == block
    # ties break to the larger block
    assert autotune.pick_tile_block({16: 100, 32: 100, 64: 200}) == 32


def test_autotune_deterministic_and_fingerprint_pinned(tiny_ds):
    kw = dict(tune_blocks=(16, 32), **ALL_BCSR)
    p1 = _pipe(tiny_ds, **kw).plan("train")
    p2 = _pipe(tiny_ds, **kw).plan("train")
    assert p1.fingerprint == p2.fingerprint
    assert np.array_equal(p1.batch_backend, p2.batch_backend)
    assert np.array_equal(p1.batch_block_f, p2.batch_block_f)
    assert np.array_equal(p1.cache.fields["tile_vals"],
                          p2.cache.fields["tile_vals"])
    # the autotuner knobs are pinned by the fingerprint: changing the sweep
    # (or kappa) yields a DIFFERENT artifact identity, so a cached plan can
    # never silently serve another tuning config's decisions
    assert _pipe(tiny_ds, **ALL_BCSR).fingerprint("train") \
        != _pipe(tiny_ds, tune_blocks=(16, 32), **ALL_BCSR) \
        .fingerprint("train")
    assert _pipe(tiny_ds, **ALL_BCSR).fingerprint("train") \
        != _pipe(tiny_ds, **ALL_SEG).fingerprint("train")


def test_plan_stores_decisions_and_stats(tiny_ds):
    plan = _pipe(tiny_ds, **ALL_BCSR).plan("train")
    assert plan.batch_backend is not None
    assert plan.batch_backends() == ["bcsr"] * len(plan)
    assert list(plan.batch_block_fs()) == [0] * len(plan)
    stats = plan.meta["batch_stats"]
    assert len(stats) == len(plan)
    for s in stats:
        assert {"nodes", "edges", "avg_degree", "tile_fill",
                "backend", "block_f"} <= set(s)
        assert s["backend"] == "bcsr"
    json.dumps(stats)                     # meta must stay JSON-serializable
    seg = _pipe(tiny_ds, **ALL_SEG).plan("train")
    assert seg.batch_backends() == ["segment"] * len(seg)


# ------------------------------------------------- plan format v3 / v2
def test_plan_v3_save_load_roundtrip(tiny_ds, tmp_path):
    plan = _pipe(tiny_ds, **ALL_BCSR).plan("train")
    path = str(tmp_path / "plan.npz")
    plan.save(path)
    loaded = Plan.load(path)
    assert np.array_equal(loaded.batch_backend, plan.batch_backend)
    assert np.array_equal(loaded.batch_block_f, plan.batch_block_f)
    assert loaded.batch_backends() == plan.batch_backends()
    assert loaded.meta["batch_stats"] == plan.meta["batch_stats"]


def test_plan_v2_artifact_still_loads(tiny_ds, tmp_path):
    """A doctored v2 artifact (no decision arrays, header version 2) loads,
    and its decisions fall back to the configured backend — exactly what a
    v2 plan executed before per-batch dispatch existed."""
    plan = _pipe(tiny_ds, **ALL_SEG).plan("train")   # mixed-free baseline
    p3 = str(tmp_path / "v3.npz")
    plan.save(p3)
    with np.load(p3, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    hdr = json.loads(str(arrays.pop("__plan_json__")))
    hdr["version"] = 2
    for k in ("batch_backend", "batch_block_f"):
        arrays.pop(k, None)
        hdr["checksums"].pop(k, None)
    arrays["__plan_json__"] = np.array(json.dumps(hdr))
    p2 = str(tmp_path / "v2.npz")
    with open(p2, "wb") as f:
        np.savez(f, **arrays)
    loaded = Plan.load(p2, expect_fingerprint=plan.fingerprint)
    assert loaded.batch_backend is None
    assert loaded.batch_backends() == ["bcsr"] * len(plan)   # meta backend
    assert list(loaded.batch_block_fs()) == [0] * len(plan)
    # auto dispatch over the v2 plan = the configured backend everywhere
    cfg = _cfg(tiny_ds)
    decs = gnn_policy.batch_decisions(loaded, BackendPolicy.auto(), cfg)
    assert decs == [("bcsr", 0)] * len(plan)


def test_backend_codes_roundtrip_and_stability():
    names = ["segment", "bcsr", "dense", "bcsr"]
    codes = encode_backends(names)
    assert codes.dtype == np.int8
    assert decode_backends(codes) == names
    # serialization table is frozen: re-numbering would corrupt artifacts
    assert BACKEND_CODES == {"segment": 0, "bcsr": 1, "dense": 2}


def test_ooc_store_roundtrips_decisions(tiny_ds, tmp_path):
    from repro.ooc.store import PlanStore, write_store
    plan = _pipe(tiny_ds, **ALL_BCSR).plan("train")
    write_store(str(tmp_path / "store"), plan)
    store = PlanStore.open(str(tmp_path / "store"))
    back = store.as_plan()
    assert np.array_equal(back.batch_backend, plan.batch_backend)
    assert np.array_equal(back.batch_block_f, plan.batch_block_f)
    assert back.batch_backends() == plan.batch_backends()


def test_streamed_plan_decisions_match_resident(tiny_ds, tmp_path):
    from repro.ooc.stream import stream_plan
    from repro.ooc.store import PlanStore
    kw = dict(tune_blocks=(16, 32), **ALL_BCSR)
    resident = _pipe(tiny_ds, **kw).plan("train")
    stream_plan(_pipe(tiny_ds, **kw), "train", False, str(tmp_path / "s"))
    streamed = PlanStore.open(str(tmp_path / "s")).as_plan()
    assert streamed.fingerprint == resident.fingerprint
    assert streamed.batch_backends() == resident.batch_backends()
    assert np.array_equal(streamed.batch_block_fs(),
                          resident.batch_block_fs())
    assert np.array_equal(streamed.cache.fields["tile_vals"],
                          resident.cache.fields["tile_vals"])
    assert streamed.meta["batch_stats"] == resident.meta["batch_stats"]


# ------------------------------------------------------- spmm impls
def _bcsr_case(seed=0, n=96, f=128, block=32):
    rng = np.random.default_rng(seed)
    a = sp.random(n, n, density=0.08, random_state=seed, format="csr",
                  dtype=np.float32)
    a = (a + a.T).tocsr()
    bc = csr_to_bcsr(a.indptr, a.indices, a.data, n, n, block=block)
    x = rng.normal(size=(n, f)).astype(np.float32)
    return a, bc, x


@pytest.mark.parametrize("impl", ["stream", "fused_interpret"])
def test_spmm_impls_match_reference(impl):
    a, bc, x = _bcsr_case()
    want = np.asarray(spmm_bcsr(bc.tile_cols, bc.tile_vals, x,
                                impl="reference"))
    got = np.asarray(spmm_bcsr(bc.tile_cols, bc.tile_vals, x, impl=impl,
                               block_f=64))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(want, a @ x, atol=1e-4, rtol=1e-5)


def test_spmm_stream_vjp_is_transpose():
    a, bc, x = _bcsr_case(seed=3, f=16)
    g = np.random.default_rng(4).normal(size=x.shape).astype(np.float32)
    _, vjp = jax.vjp(
        lambda x_: spmm_bcsr_sym(bc.tile_cols, bc.tile_vals, x_,
                                 impl="stream"), x)
    (dx,) = vjp(g)
    np.testing.assert_allclose(np.asarray(dx), a.T @ g, atol=1e-4)


# ----------------------------------------- auto-vs-forced bitwise parity
@pytest.mark.parametrize("pin, forced", [(ALL_BCSR, "bcsr"),
                                         (ALL_SEG, "segment")])
def test_engine_auto_matches_forced_bitwise(tiny_ds, pin, forced):
    plan = _pipe(tiny_ds, **pin).plan("test", for_inference=True)
    assert plan.batch_backends() == [forced] * len(plan)
    cfg = _cfg(tiny_ds)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    q = plan.routing.node_ids
    auto = GNNInferenceEngine(plan, cfg, params, backend="auto",
                              cache_batches=0)
    force = GNNInferenceEngine(plan, cfg, params, backend=forced,
                               cache_batches=0)
    assert np.array_equal(auto.query(q), force.query(q))


@pytest.mark.parametrize("pin, forced", [(ALL_BCSR, "bcsr"),
                                         (ALL_SEG, "segment")])
def test_trainer_auto_matches_forced_bitwise(tiny_ds, pin, forced):
    pipe = _pipe(tiny_ds, **pin)
    tr = pipe.plan("train")
    va = pipe.plan("val", for_inference=True)
    cfg = _cfg(tiny_ds, dropout=0.3)
    kw = dict(lr=1e-3, seed=0)
    res_a = GNNTrainer(cfg, backend="auto", **kw).fit(
        tr, va, tiny_ds.num_classes, epochs=2)
    res_f = GNNTrainer(cfg, backend=forced, **kw).fit(
        tr, va, tiny_ds.num_classes, epochs=2)
    for ha, hf in zip(res_a.history, res_f.history):
        assert ha["train_loss"] == hf["train_loss"]
        assert ha["val_loss"] == hf["val_loss"]
    for a, b in zip(jax.tree_util.tree_leaves(res_a.params),
                    jax.tree_util.tree_leaves(res_f.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_executor_auto_matches_forced_bitwise(tiny_ds):
    """Auto dispatch through the shard_map super-step path (1-device mesh
    runs the full machinery on tier-1) — bitwise vs the forced backend."""
    from repro.dist.data_parallel import data_mesh
    pipe = _pipe(tiny_ds, **ALL_BCSR)
    tr = pipe.plan("train")
    va = pipe.plan("val", for_inference=True)
    cfg = _cfg(tiny_ds, dropout=0.3)
    res_a = GNNTrainer(cfg, backend="auto", lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=2, mesh=data_mesh(1))
    res_f = GNNTrainer(cfg, backend="bcsr", lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=2, mesh=data_mesh(1))
    for a, b in zip(jax.tree_util.tree_leaves(res_a.params),
                    jax.tree_util.tree_leaves(res_f.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mixed_plan_dispatches_per_batch(tiny_ds):
    """With a mid kappa the plan may mix backends; whatever it decided, the
    engine's auto answers must match the all-segment forced engine to fp32
    tolerance (different backends = different float association, so this is
    allclose, not bitwise), and the stored decisions drive the dispatch."""
    plan = _pipe(tiny_ds, **ALL_BCSR).plan("test", for_inference=True)
    cfg = _cfg(tiny_ds)
    decs = gnn_policy.batch_decisions(plan, BackendPolicy.auto(), cfg)
    assert decs == list(zip(plan.batch_backends(),
                            (int(x) for x in plan.batch_block_fs())))
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    q = plan.routing.node_ids
    auto = GNNInferenceEngine(plan, cfg, params, backend="auto",
                              cache_batches=0)
    seg = GNNInferenceEngine(plan, cfg, params, backend="segment",
                             cache_batches=0)
    np.testing.assert_allclose(auto.query(q), seg.query(q), atol=1e-4)


def test_gat_auto_resolves_to_segment(tiny_ds):
    plan = _pipe(tiny_ds, **ALL_BCSR).plan("test", for_inference=True)
    cfg = _cfg(tiny_ds, kind="gat", heads=2)
    decs = gnn_policy.batch_decisions(plan, BackendPolicy.auto(), cfg)
    assert decs == [("segment", 0)] * len(plan)


# ------------------------------------- one decision list for every consumer
MIXED = dict(autotune=True, auto_kappa=16.0, tune_block_fs=(16, 32))


def _no_trace(*a, **k):
    raise AssertionError("the forward was traced before the tile check")


def _fit_tileless_plan(tiny_ds, cfg, val, monkeypatch):
    import repro.train.gnn_trainer as gt
    monkeypatch.setattr(gt, "gnn_apply", _no_trace)
    GNNTrainer(cfg, backend=BackendPolicy.fixed("bcsr")).fit(
        _pipe(tiny_ds, backend="segment").plan("train"), val,
        tiny_ds.num_classes, epochs=1)


def _fit_batcher(tiny_ds, cfg, val, monkeypatch):
    import repro.train.gnn_trainer as gt
    from repro.graph.sampling import make_batcher
    monkeypatch.setattr(gt, "gnn_apply", _no_trace)
    bt = make_batcher("cluster_gcn", tiny_ds, split="train", num_batches=2)
    GNNTrainer(cfg, backend=BackendPolicy.fixed("bcsr")).fit(
        bt, val, tiny_ds.num_classes, epochs=1)


def _executor_evaluate(tiny_ds, cfg, val, monkeypatch):
    import repro.dist.data_parallel as dp
    monkeypatch.setattr(dp, "gnn_apply", _no_trace)
    ex = dp.ShardedPlanExecutor(dp.data_mesh(1), cfg,
                                backend=BackendPolicy.fixed("bcsr"))
    seg = _pipe(tiny_ds, backend="segment").plan("val", for_inference=True)
    ex.evaluate(init_gnn(cfg, jax.random.PRNGKey(0)), seg.cache)


@pytest.mark.parametrize("consumer", [_fit_tileless_plan, _fit_batcher,
                                      _executor_evaluate],
                         ids=["fit-plan", "fit-batcher", "executor"])
def test_bcsr_on_tileless_batches_fails_before_trace(tiny_ds, monkeypatch,
                                                     consumer):
    """A fixed bcsr policy over batches without tiles is refused by the
    policy's one check, before any forward is traced."""
    val = _pipe(tiny_ds).plan("val", for_inference=True)     # tiled
    with pytest.raises(ValueError, match="bcsr"):
        consumer(tiny_ds, _cfg(tiny_ds), val, monkeypatch)


@pytest.mark.parametrize("consumer", ["trainer", "engine", "executor"])
def test_consumers_dispatch_the_stored_decisions(tiny_ds, consumer):
    """Under the auto policy every consumer runs, batch by batch, the
    (backend, block_f) the plan stored: a plan that mixes backends and
    tuned widths gives each the same list."""
    from repro.dist.data_parallel import ShardedPlanExecutor, data_mesh
    plan = _pipe(tiny_ds, **MIXED).plan("train")
    want = list(zip(plan.batch_backends(),
                    (int(x) for x in plan.batch_block_fs())))
    assert {b for b, _ in want} == {"segment", "bcsr"}
    assert {f for _, f in want} == {0, 32}
    cfg = _cfg(tiny_ds)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    if consumer == "trainer":
        tr = GNNTrainer(cfg, backend=BackendPolicy.auto())
        got = []

        def steps_for(backend, block_f=0):
            got.append((backend, block_f))
            return {"eval": lambda params, batch: (0.0, 0.0, 1.0)}

        tr._steps_for = steps_for
        tr.evaluate(params, plan)
    elif consumer == "engine":
        got = GNNInferenceEngine(plan, cfg, params,
                                 backend=BackendPolicy.auto())._decisions
    else:
        got = ShardedPlanExecutor(data_mesh(1), cfg,
                                  backend=BackendPolicy.auto()
                                  ).decisions(plan)
    assert got == want
