import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
extract memory/cost/collective analysis for the roofline (EXPERIMENTS.md).

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k --mesh single --out results/dryrun

Cells and meshes:
  * mesh "single"  = (data=16, model=16), 256 chips — roofline source.
  * mesh "multi"   = (pod=2, data=16, model=16), 512 chips — proves the pod
    axis shards.
  * --arch all --shape all runs every applicable cell (long_500k only for
    sub-quadratic archs).

Everything is abstract (ShapeDtypeStruct): no parameter or cache memory is
ever allocated; only XLA compilation happens on this host.
"""
import argparse
import dataclasses
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, ARCH_IDS
from repro.configs.shapes import SHAPES, ShapeSpec, shape_applies
from repro.dist.logical import logical_rules
from repro.dist.sharding import (
    param_spec, opt_spec, cache_spec, batch_spec, tree_shardings,
    with_shardings, logical_rules_for)
from repro.launch.mesh import make_production_mesh
from repro.models.lm import (
    LMConfig, abstract_params, abstract_cache, lm_loss, decode_step, lm_forward)
from repro.models.lm.model import head_logits
from repro.optim.optimizers import get_optimizer

# ---------------------------------------------------------- hardware constants
PEAK_FLOPS = 197e12        # bf16 per chip (TPU v5e)
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 45e9              # bytes/s effective per link (assignment: ~50 GB/s)


def optimizer_for(arch: str) -> str:
    """Adafactor for ≥100B params (optimizer state must stay sub-HBM)."""
    return "adafactor" if arch in ("deepseek-v3-671b", "command-r-plus-104b") \
        else "adamw"


# ------------------------------------------------------------------ input specs
def input_specs(cfg: LMConfig, shape: ShapeSpec, mesh) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    mk = lambda shp, dt, name: jax.ShapeDtypeStruct(
        shp, dt, sharding=NamedSharding(mesh, batch_spec(mesh, name, shp)))
    if shape.kind in ("train", "prefill"):
        if cfg.num_codebooks > 1:
            toks = mk((b, s, cfg.num_codebooks), jnp.int32, "tokens")
        else:
            toks = mk((b, s), jnp.int32, "tokens")
        batch = {"tokens": toks, "loss_mask": mk((b, s), jnp.float32, "loss_mask")}
        if cfg.vision_prefix_len:
            batch["prefix_embeds"] = mk(
                (b, cfg.vision_prefix_len, cfg.d_model), jnp.dtype(cfg.dtype),
                "prefix_embeds")
        return batch
    # decode: one new token against a seq_len cache
    if cfg.num_codebooks > 1:
        toks = mk((b, 1, cfg.num_codebooks), jnp.int32, "tokens")
    else:
        toks = mk((b, 1), jnp.int32, "tokens")
    return {"tokens": toks,
            "pos": jax.ShapeDtypeStruct((), jnp.int32,
                                        sharding=NamedSharding(mesh, P()))}


# ------------------------------------------------------------------- step fns
def make_train_step(cfg: LMConfig, opt):
    def train_step(params, opt_state, batch, lr):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(cfg, p, batch, remat=True))(params)
        updates, opt_state = opt.update(grads, opt_state, params, lr)
        new_params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates)
        return new_params, opt_state, loss
    return train_step


def make_prefill_step(cfg: LMConfig):
    def prefill(params, batch):
        prefix = batch.get("prefix_embeds")
        h = lm_forward(cfg, params, batch["tokens"], prefix_embeds=prefix,
                       remat=False)
        return head_logits(cfg, params, h[:, -1])
    return prefill


def make_decode_step(cfg: LMConfig):
    def serve_step(params, cache, batch):
        logits, cache = decode_step(cfg, params, cache, batch["tokens"],
                                    batch["pos"])
        return logits, cache
    return serve_step


# ---------------------------------------------------------- collective parsing
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b(f32|f16|bf16|s32|u32|s8|u8|pred|f64|s64|c64)\[([0-9,]*)\]")
_BYTES = {"f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "c64": 8}
# ring all-reduce moves ~2x the buffer; others ~1x
_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
           "all-to-all": 1.0, "collective-permute": 1.0}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-shape bytes of every collective op (per-device shapes in the
    SPMD-partitioned module), weighted by a ring-cost factor."""
    out = {k: 0.0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r".*= *((?:\([^)]*\)|\S+)) (all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)", ls)
        if not m:
            continue
        op = m.group(2)
        # parse every typed shape on the lhs (handles tuple outputs)
        lhs = ls.split("=")[0] + "=" + m.group(1)
        nbytes = 0
        for t, dims in _SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _BYTES[t]
        out[op] += nbytes * _FACTOR[op]
        count[op] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count
    return out


def save_hlo(hlo_text: str, out_dir: str, tag: str) -> None:
    """Store the partitioned HLO (zstd) so roofline re-analysis after parser
    improvements never needs a recompile."""
    try:
        import zstandard as zstd
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".hlo.zst"), "wb") as f:
            f.write(zstd.ZstdCompressor(level=3).compress(hlo_text.encode()))
    except Exception:
        pass


# ------------------------------------------------------------------ GNN cells
# The paper's own model, distributed the IBMB way: every chip processes its
# own precomputed padded batch (pure DP over the flattened mesh — batches are
# independent by construction), gradients all-reduced. Shapes follow the
# products-like synthetic config at production padding.
GNN_SHAPE = dict(max_nodes=8192, max_edges=131072, max_outputs=1024,
                 feat_dim=100, num_classes=47, hidden=256, layers=3)


def run_gnn_cell(arch: str, mesh_kind: str, verbose: bool = True,
                 hlo_dir: Optional[str] = None) -> Dict[str, Any]:
    from repro.models.gnn.models import (
        GNNConfig, init_gnn, gnn_apply, output_logits, masked_xent)
    kind = arch.split("-", 1)[1]
    g = GNN_SHAPE
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    all_axes = tuple(mesh.axis_names)
    nb = n_chips                        # one IBMB batch per chip per step
    cfg = GNNConfig(kind=kind, in_dim=g["feat_dim"], hidden=g["hidden"],
                    out_dim=g["num_classes"], num_layers=g["layers"],
                    dtype=os.environ.get("REPRO_GNN_DTYPE", "float32"))

    params_abs = jax.eval_shape(
        lambda k: __import__("repro.models.gnn.models", fromlist=["init_gnn"])
        .init_gnn(cfg, k), jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    params_in = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=rep),
        params_abs)

    def field(shape, dt):
        return jax.ShapeDtypeStruct(
            (nb,) + shape, dt,
            sharding=NamedSharding(mesh, P(all_axes)))
    batch = {
        "edge_src": field((g["max_edges"],), jnp.int32),
        "edge_dst": field((g["max_edges"],), jnp.int32),
        "edge_weight": field((g["max_edges"],), jnp.float32),
        "node_mask": field((g["max_nodes"],), jnp.float32),
        "output_idx": field((g["max_outputs"],), jnp.int32),
        "output_mask": field((g["max_outputs"],), jnp.float32),
        "features": field((g["max_nodes"], g["feat_dim"]), jnp.float32),
        "labels": field((g["max_outputs"],), jnp.int32),
    }
    opt = get_optimizer("adamw")
    opt_abs = jax.eval_shape(opt.init, params_abs)
    opt_in = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=rep),
        opt_abs)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)

    def loss_fn(p, b):
        def one(b1):
            h = gnn_apply(cfg, p, b1)
            lg = output_logits(h, b1)
            return masked_xent(lg, b1["labels"], b1["output_mask"])
        return jax.vmap(one)(b).mean()

    if os.environ.get("REPRO_GNN_SHMAP", "0") == "1":
        # §Perf C1: IBMB batches are independent by construction — shard_map
        # makes each chip compute ITS batch locally and psum only gradients.
        # The vmap/SPMD baseline loses the batch sharding through the
        # (NB·nodes, F) reshape inside dot lowering and replicates all
        # batches' compute on every chip.
        from jax import shard_map
        from jax.sharding import PartitionSpec as P2

        def local_grads(p, b):
            b1 = jax.tree_util.tree_map(lambda x: x[0], b)   # my one batch
            h = gnn_apply(cfg, p, b1)
            lg = output_logits(h, b1)
            loss = masked_xent(lg, b1["labels"], b1["output_mask"])
            loss, grads = jax.value_and_grad(
                lambda q: masked_xent(output_logits(gnn_apply(cfg, q, b1), b1),
                                      b1["labels"], b1["output_mask"]))(p)
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, all_axes), grads)
            return jax.lax.pmean(loss, all_axes), grads

        sm = shard_map(local_grads, mesh=mesh,
                       in_specs=(P2(), P2(all_axes)),
                       out_specs=(P2(), P2()), check_vma=False)

        def train_step(p, s, b, lr):
            loss, grads = sm(p, b)
            u, s = opt.update(grads, s, p, lr)
            p = jax.tree_util.tree_map(
                lambda a, x: (a + x).astype(a.dtype), p, u)
            return p, s, loss
    else:
        def train_step(p, s, b, lr):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            u, s = opt.update(grads, s, p, lr)
            p = jax.tree_util.tree_map(
                lambda a, x: (a + x).astype(a.dtype), p, u)
            return p, s, loss

    t0 = time.time()
    with mesh:
        lowered = jax.jit(train_step, donate_argnums=(0, 1)).lower(
            params_in, opt_in, batch, lr)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return _finish(arch, "train_products", mesh_kind, n_chips, cfg, None,
                   compiled, t_lower, t_compile, hlo_dir,
                   model_flops_override=_gnn_model_flops(g, nb))


def _gnn_model_flops(g, nb) -> float:
    """Useful FLOPs: 3 layers of (node matmul + edge aggregation), fwd+bwd."""
    dense = g["max_nodes"] * (g["feat_dim"] * g["hidden"] +
                              g["hidden"] * g["hidden"] +
                              g["hidden"] * g["num_classes"])
    agg = g["max_edges"] * (g["hidden"] * 2 + g["num_classes"])
    return float(nb * (2 * dense + 2 * agg) * 3)     # ×3 fwd+bwd


# ----------------------------------------------------------------------- cell
def run_cell(arch: str, shape_name: str, mesh_kind: str,
             verbose: bool = True, hlo_dir: Optional[str] = None) -> Dict[str, Any]:
    if arch.startswith("gnn-"):
        return run_gnn_cell(arch, mesh_kind, verbose=verbose, hlo_dir=hlo_dir)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applies(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": "full-attention arch, long_500k needs sub-quadratic"}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    params_abs = abstract_params(cfg)
    params_sh = tree_shardings(mesh, params_abs, param_spec)
    params_in = with_shardings(params_abs, params_sh)
    batch = input_specs(cfg, shape, mesh)
    rules = logical_rules_for(cfg, mesh)

    with mesh, logical_rules(rules, mesh=mesh):
        if shape.kind == "train":
            opt = get_optimizer(optimizer_for(arch))
            opt_abs = jax.eval_shape(opt.init, params_abs)
            opt_sh = tree_shardings(
                mesh, opt_abs, lambda m, p, l: opt_spec(m, p, l, {}))
            opt_in = with_shardings(opt_abs, opt_sh)
            lr = jax.ShapeDtypeStruct((), jnp.float32,
                                      sharding=NamedSharding(mesh, P()))
            step = make_train_step(cfg, opt)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params_in, opt_in, batch, lr)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg)
            lowered = jax.jit(step).lower(params_in, batch)
        else:  # decode
            cache_abs = abstract_cache(cfg, shape.global_batch, shape.seq_len)
            cache_sh = tree_shardings(mesh, cache_abs, cache_spec)
            cache_in = with_shardings(cache_abs, cache_sh)
            step = make_decode_step(cfg)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                params_in, cache_in, batch)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    return _finish(arch, shape_name, mesh_kind, n_chips, cfg, shape, compiled,
                   t_lower, t_compile, hlo_dir, verbose=verbose)


def _finish(arch, shape_name, mesh_kind, n_chips, cfg, shape, compiled,
            t_lower, t_compile, hlo_dir, model_flops_override=None,
            verbose=False) -> Dict[str, Any]:
    from repro.launch.hlo_analysis import analyze_hlo
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    if hlo_dir:
        save_hlo(hlo_text, hlo_dir, f"{arch}__{shape_name}__{mesh_kind}")
    # trip-count-aware accounting (XLA's cost_analysis counts scan bodies once)
    hlo = analyze_hlo(hlo_text)
    flops = float(hlo["flops"])              # per chip per step
    bytes_hbm = float(hlo["bytes"])
    coll_total = float(hlo["collective_bytes"])

    # MODEL_FLOPS: 6·N_active·tokens (train), 2·N_active·tokens (fwd-only)
    if model_flops_override is not None:
        model_flops = model_flops_override
        n_active = params_n = None
    else:
        n_active = cfg.active_param_count()
        params_n = cfg.param_count()
        if shape.kind == "train":
            tokens = shape.global_batch * shape.seq_len
            model_flops = 6.0 * n_active * tokens
        elif shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            model_flops = 2.0 * n_active * tokens
        else:
            model_flops = 2.0 * n_active * shape.global_batch  # 1 token/seq
    model_flops_chip = model_flops / n_chips

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": n_chips,
        "params": params_n, "active_params": n_active,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0) +
                          (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "cost_xla_once": {"flops": float(cost.get("flops", 0.0)),
                          "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "hlo": {"flops": flops, "bytes": bytes_hbm,
                "collective_bytes": coll_total,
                "collectives": hlo["coll"]},
        "model_flops_per_chip": model_flops_chip,
        "useful_ratio": model_flops_chip / flops if flops else None,
        "roofline": {
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_hbm / HBM_BW,
            "collective_s": coll_total / ICI_BW,
        },
    }
    r = result["roofline"]
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: r[k])
    result["roofline"]["dominant"] = dom
    # roofline fraction: useful compute time / bound time
    bound = max(r["compute_s"], r["memory_s"], r["collective_s"])
    result["roofline"]["model_compute_s"] = model_flops_chip / PEAK_FLOPS
    result["roofline"]["roofline_fraction"] = \
        (model_flops_chip / PEAK_FLOPS) / bound if bound else None
    if verbose:
        print(json.dumps(result, indent=2, default=str))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        arch_shapes = ["train_products"] if arch.startswith("gnn-") else shapes
        for shape in arch_shapes:
            for mesh_kind in meshes:
                tag = f"{arch}__{shape}__{mesh_kind}".replace("/", "_")
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip] {tag} (cached)")
                    continue
                print(f"[cell] {tag}")
                try:
                    res = run_cell(arch, shape, mesh_kind, verbose=False,
                                   hlo_dir=os.path.join(args.out, "hlo"))
                except Exception as e:  # record failures — they are bugs
                    import traceback
                    res = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-3000:]}
                    print(res["error"])
                with open(path, "w") as f:
                    json.dump(res, f, indent=2, default=str)
                if "roofline" in res:
                    r = res["roofline"]
                    print(f"  compute {r['compute_s']:.3e}s  memory {r['memory_s']:.3e}s  "
                          f"collective {r['collective_s']:.3e}s  → {r['dominant']}")


if __name__ == "__main__":
    main()
