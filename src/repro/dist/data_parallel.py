"""Data-parallel Plan execution over a device mesh (DESIGN.md §9).

The paper's amortization precomputes fixed-shape batches; the next scale
lever is executing those frozen batches across a mesh instead of one device
at a time. The unit of multi-device work is the **super-step**: the Plan's
schedule is grouped into consecutive runs of `world` batches (`world` =
product of the mesh's data-axis sizes), every device takes one batch, and
one `shard_map`-ed forward/backward runs per super-step with a `psum`
gradient mean — semantically identical to single-device training with
gradient accumulation over `world` micro-batches.

Spec choices (DESIGN.md §9):

* **batches shard, params replicate.** Every stacked batch field gets its
  leading (super-step) dim partitioned over the mesh's data axes; GNN
  params/optimizer state are small, so they follow `repro.dist.sharding`'s
  "replication is always correct" policy — `replicated_shardings` routes
  through the same `fit_spec`/`tree_shardings` machinery as the LM stack.
* **ragged tails pad with weight 0.** All batches in one Plan already share
  ONE padded shape bucket (BatchCache stacks them contiguously and records
  the real counts in its padding meta), so the only raggedness left is the
  last super-step of an epoch: it is padded by repeating the final real
  batch with weight 0, and the weighted `psum` mean divides by the REAL
  count — bitwise the same update `GradAccumulator.flush` would apply.
* **backends.** Every aggregation backend runs under `shard_map`. The bcsr
  SpMM off-TPU is the compiled streaming path (`spmm_bcsr_stream` — plain
  XLA scan, DESIGN.md §14), so it partitions exactly like the segment
  gather + segment-sum; on TPU it is the fused Pallas kernel, invoked
  per-device inside the manually partitioned body. Backend selection is a
  `BackendPolicy` (fixed or per-batch auto from the plan's autotuned
  decisions); the executor keeps one set of super-step executables per
  (backend, block_f) decision, built lazily.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.sharding import data_axes, fit_spec, tree_shardings
from repro.models.gnn import policy as gnn_policy
from repro.models.gnn.models import (
    GNNConfig, gnn_apply, masked_xent, output_logits,
)
from repro.optim.optimizers import apply_updates


# ------------------------------------------------------------------- meshes
def data_mesh(num_devices: Optional[int] = None) -> Mesh:
    """A 1-D pure data-parallel mesh over (the first `num_devices` of) the
    local devices — the mesh `GNNTrainer.fit(mesh=...)` and
    `GNNInferenceEngine(mesh=...)` expect. On CPU, fake an 8-way mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before jax
    initializes)."""
    devs = jax.devices()
    n = len(devs) if num_devices is None else num_devices
    if n < 1 or n > len(devs):
        raise ValueError(f"num_devices={num_devices} but {len(devs)} present")
    return Mesh(np.array(devs[:n]), ("data",))


def mesh_world(mesh: Mesh) -> int:
    """Batches per super-step: the product of the mesh's data-axis sizes."""
    dp = data_axes(mesh)
    if not dp:
        raise ValueError(
            f"mesh {mesh.axis_names} has no data axis ('data'/'pod') — "
            "data-parallel Plan execution needs one")
    w = 1
    for a in dp:
        w *= mesh.shape[a]
    return w


# --------------------------------------------------------------- super-steps
def superstep_indices(order: Sequence[int], world: int
                      ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Group a schedule into device-count-sized super-steps.

    Returns a list of ``(idx, weight)`` pairs, each of length `world`:
    `idx` are batch indices into the cache, `weight` is 1.0 for real
    entries and 0.0 for the ragged-tail pads (which repeat the last real
    batch — same shape bucket, zero contribution to the psum mean)."""
    order = np.asarray(order, dtype=np.int64)
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    steps = []
    for s in range(0, len(order), world):
        chunk = order[s:s + world]
        pad = world - len(chunk)
        idx = np.concatenate([chunk, np.full(pad, chunk[-1], np.int64)])
        w = np.concatenate([np.ones(len(chunk), np.float32),
                            np.zeros(pad, np.float32)])
        steps.append((idx, w))
    return steps


def stack_batches(host, idx: np.ndarray) -> Dict[str, np.ndarray]:
    """Stack batches `idx` of an indexable host container into one
    super-step: every field gains a leading axis of length len(idx).

    Fast path: a ``BatchCache`` (or a ``Plan``'s cache) answers with one
    fancy-index per contiguous field block. All selected batches must share
    one shape bucket — guaranteed within a Plan, asserted otherwise.

    A host exposing ``stack(idx)`` (the out-of-core ``LazyBatchCache``,
    DESIGN.md §13) wins over the fields fast path: its members must come
    through the checksum-verified, LRU-budgeted per-batch read — fancy-
    indexing its memmaps would silently skip both."""
    stack = getattr(host, "stack", None)
    if stack is not None:                        # verified lazy path (§13)
        return stack(np.asarray(idx))
    fields = getattr(host, "fields", None)
    if fields is not None:                       # BatchCache fast path
        return {k: v[idx] for k, v in fields.items()}
    dicts = [host[int(i)] for i in idx]
    for d in dicts[1:]:
        assert all(np.shape(d[k]) == np.shape(dicts[0][k]) for k in d), \
            "super-step members must share one padded shape bucket"
    return {k: np.stack([d[k] for d in dicts]) for k in dicts[0]}


# ------------------------------------------------------------------- specs
def replicated_shardings(mesh: Mesh, tree):
    """Replicate every leaf of `tree` on `mesh` — the executor's param/opt
    policy. GNN parameter trees are small (DESIGN.md §9), and replication
    is always correct (`repro.dist.sharding`'s fallback rule); routed
    through `fit_spec` so the behaviour matches the rest of the dist
    layer (an empty axes tuple fits every shape)."""
    return tree_shardings(
        mesh, tree, lambda m, path, leaf: fit_spec(m, leaf.shape, ()))


def superstep_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a stacked super-step field of any rank: leading
    (super-step) dim over the mesh's data axes, everything else
    replicated."""
    dp = data_axes(mesh)
    return NamedSharding(mesh, P(dp[0] if len(dp) == 1 else dp))


def replicate(tree, mesh: Mesh):
    """device_put `tree` fully replicated across `mesh`."""
    return jax.device_put(tree, replicated_shardings(mesh, tree))


# --------------------------------------------------------------- the executor
@dataclasses.dataclass(frozen=True)
class SuperstepFns:
    """One decision's jit'd super-step executables (DESIGN.md §9/§14)."""
    train: "object"
    eval: "object"
    forward: "object"


class ShardedPlanExecutor:
    """Execute a Plan's schedule data-parallel over `mesh` (DESIGN.md §9).

    Owns the jit'd super-step executables — train (forward/backward +
    psum-mean gradients + optimizer update), eval (per-device masked
    loss/accuracy sums) and forward (per-device output logits, consumed by
    ``GNNInferenceEngine``) — one set per (backend, block_f) decision,
    built lazily and traced ONCE each since all super-steps share one
    stacked shape. Every backend (segment, bcsr, dense) runs inside the
    ``shard_map`` body: the bcsr SpMM is ordinary compiled XLA off-TPU and
    the fused Pallas kernel on TPU (DESIGN.md §14), so there is no
    per-device fallback loop and ``sharded`` is always True.

    `opt` (a ``repro.optim`` Optimizer) is only needed for training.
    `backend` accepts a name, ``"auto"`` or a
    :class:`~repro.models.gnn.policy.BackendPolicy`; with an auto policy,
    callers pick the per-super-step executable via :meth:`steps_for` +
    ``policy.superstep_decision`` (``evaluate`` does this itself).
    """

    def __init__(self, mesh: Mesh, model_cfg: GNNConfig, opt=None,
                 backend=None):
        model_cfg, self.policy = gnn_policy.resolve(model_cfg, backend)
        self.mesh = mesh
        self.cfg = model_cfg
        self.opt = opt
        self.world = mesh_world(mesh)
        self.backend = model_cfg.backend
        self.sharded = True        # every backend runs under shard_map (§14)
        self.batch_sharding = superstep_sharding(mesh)
        self._steps: Dict[Tuple[str, int], "SuperstepFns"] = {}
        base = self.steps_for(self.backend,
                              int(getattr(model_cfg, "bcsr_block_f", 0)))
        # the fixed-decision executables, kept as plain attributes for the
        # single-executable callers (and back-compat)
        self.train_superstep = base.train
        self.eval_superstep = base.eval
        self.forward_superstep = base.forward

    # ------------------------------------------------------------ staging
    def replicate(self, tree):
        return replicate(tree, self.mesh)

    def supersteps(self, order) -> List[Tuple[np.ndarray, np.ndarray]]:
        return superstep_indices(order, self.world)

    def stage(self, host, idx: np.ndarray, weights: np.ndarray):
        """Stack + device_put one super-step, sharded over the data axes."""
        stacked = stack_batches(host, idx)
        stacked = jax.device_put(stacked, self.batch_sharding)
        weights = jax.device_put(np.asarray(weights, np.float32),
                                 self.batch_sharding)
        return stacked, weights

    def decisions(self, host) -> List[Tuple[str, int]]:
        """Per-batch (backend, block_f) under this executor's policy —
        the plan's stored autotuner decisions when ``host`` carries them —
        checked against ``host``'s tiles before any trace (DESIGN.md
        §14)."""
        return gnn_policy.batch_decisions(host, self.policy, self.cfg)

    # ------------------------------------------------------------- builds
    def steps_for(self, backend: str, block_f: int = 0) -> "SuperstepFns":
        """The (train, eval, forward) super-step executables for one
        (backend, block_f) decision — built lazily, cached for the
        executor's lifetime (one trace per decision in play)."""
        key = (backend, int(block_f))
        if key not in self._steps:
            self._steps[key] = self._build(backend, int(block_f))
        return self._steps[key]

    def _build(self, backend: str, block_f: int) -> "SuperstepFns":
        cfg = gnn_policy.batch_config(self.cfg, backend, block_f)
        P_rep, P_dp = P(), self.batch_sharding.spec

        def loss_fn(params, batch, rng):
            h = gnn_apply(cfg, params, batch, rng=rng, train=rng is not None)
            with jax.named_scope("loss"):
                logits = output_logits(h, batch)
                return masked_xent(logits, batch["labels"],
                                   batch["output_mask"])

        def eval_fn(params, batch):
            h = gnn_apply(cfg, params, batch, train=False)
            logits = output_logits(h, batch)
            msk = batch["output_mask"]
            loss = masked_xent(logits, batch["labels"], msk)
            acc = ((logits.argmax(-1) == batch["labels"]).astype(jnp.float32)
                   * msk).sum()
            return loss * msk.sum(), acc, msk.sum()

        def _one(tree):               # strip the per-device leading dim of 1
            return jax.tree_util.tree_map(lambda x: x[0], tree)

        # the reduction axes must be exactly the axes the super-step is
        # sharded over — a ('pod', 'data') mesh psums over both, or the
        # replicas silently diverge
        dp = data_axes(self.mesh)

        # --- sharded bodies: each device holds ONE batch of the super-step
        def train_body(params, batch, w, rng):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, _one(batch), rng[0])
            w = w[0]
            denom = jax.lax.psum(w, dp)
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g * w, dp) / denom, grads)
            return grads, loss[None]

        def eval_body(params, batch, w):
            l, a, n = eval_fn(params, _one(batch))
            w = w[0]
            return (l * w)[None], (a * w)[None], (n * w)[None]

        def fwd_body(params, batch):
            b = _one(batch)
            h = gnn_apply(cfg, params, b, train=False)
            return output_logits(h, b)[None]

        mesh = self.mesh

        @partial(jax.jit, donate_argnums=(0, 1))
        def train_superstep(params, opt_state, batch, weights, lr, rngs):
            grads, losses = shard_map(
                train_body, mesh=mesh,
                in_specs=(P_rep, P_dp, P_dp, P_dp),
                out_specs=(P_rep, P_dp), check_vma=False)(
                params, batch, weights, rngs)
            with jax.named_scope("optimizer"):
                updates, opt_state = self.opt.update(
                    grads, opt_state, params, lr)
                params = apply_updates(params, updates)
            return params, opt_state, losses

        @jax.jit
        def eval_superstep(params, batch, weights):
            return shard_map(
                eval_body, mesh=mesh,
                in_specs=(P_rep, P_dp, P_dp),
                out_specs=(P_dp, P_dp, P_dp), check_vma=False)(
                params, batch, weights)

        @jax.jit
        def forward_superstep(params, batch):
            return shard_map(
                fwd_body, mesh=mesh,
                in_specs=(P_rep, P_dp),
                out_specs=P_dp, check_vma=False)(params, batch)

        return SuperstepFns(train_superstep, eval_superstep,
                            forward_superstep)

    # ---------------------------------------------------------- evaluation
    def evaluate(self, params, host, decisions=None) -> Dict[str, float]:
        """Mini-batched evaluation over every batch of `host`, mesh-
        parallel; numerically the per-batch sums of the single-device
        ``GNNTrainer.evaluate``. Under an auto policy each super-step runs
        the executable its group's stored decision selects
        (``policy.superstep_decision``); pass ``decisions`` when `host` is
        a bare cache whose owning Plan carried the stored decisions."""
        if decisions is None:
            decisions = self.decisions(host)
        tot_l = tot_a = tot_n = 0.0
        for idx, w in self.supersteps(np.arange(len(host))):
            fns = self.steps_for(
                *gnn_policy.superstep_decision(decisions, idx))
            batch, wd = self.stage(host, idx, w)
            l, a, n = fns.eval(params, batch, wd)
            tot_l += float(np.sum(l)); tot_a += float(np.sum(a))
            tot_n += float(np.sum(n))
        n = max(tot_n, 1.0)
        return {"loss": tot_l / n, "acc": tot_a / n}
