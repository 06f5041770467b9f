"""GNN training loop reproducing the paper's recipe (Sec. 4 / App. B):

Adam + ReduceLROnPlateau(0.33, patience, cooldown) on val loss, early stop on
val loss, batch scheduling (TSP / weighted / none), optional gradient
accumulation, mini-batched evaluation with the SAME method used for training
("since full inference is too slow to execute every epoch").

One jit'd train_step / eval_step serves every method because all batchers
emit identical static shapes (per method).

``fit`` writes profiler spans (DESIGN.md §16): ``ibmb/fit/epoch``,
``ibmb/fit/schedule``, ``ibmb/fit/step`` (a ``StepTraceAnnotation``),
``ibmb/fit/loss_sync`` and ``ibmb/fit/eval``; the steps' device ops carry
the model's named scopes plus ``loss`` and ``optimizer``.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.batches import BatchCache, PaddedBatch
from repro.core.plan import Plan
from repro.core.scheduling import make_schedule
from repro.data.loader import PrefetchLoader
from repro.faults import FaultStats
from repro.models.gnn import policy as gnn_policy
from repro.models.gnn.models import (
    GNNConfig, init_gnn, gnn_apply, output_logits, masked_xent, masked_accuracy,
)
from repro.optim.optimizers import get_optimizer, apply_updates
from repro.optim.schedules import ReduceLROnPlateau
from repro.optim.accumulate import GradAccumulator


class NonFiniteGradError(RuntimeError):
    """Raised by ``nonfinite_policy="halt"`` when a step produces NaN/Inf
    loss or gradients (DESIGN.md §12) — training stops at the first
    poisoned step instead of silently corrupting the parameters."""


@dataclasses.dataclass
class TrainResult:
    params: Dict                 # best-validation params; the last ones if no eval ran
    history: List[Dict]          # per-epoch metrics
    best_val_acc: float
    best_epoch: int
    time_per_epoch: float
    preprocess_time: float
    total_time: float


def step_rng(rng: jax.Array, epoch: int, step: int) -> jax.Array:
    """Dropout key for (epoch, step), derived statelessly from the base rng.

    Keys MUST differ across epochs for the same step: a fixed caller-passed
    rng that is merely re-split from the top every epoch replays identical
    dropout masks epoch after epoch (the regression this fixes). fold_in
    domain 1 keeps the (epoch, step) grid disjoint from the init key
    (domain 0, see ``fit``), and the same derivation drives both the
    single-device loop and the mesh super-steps — micro-batch `step` of
    epoch `epoch` sees one mask no matter how batches map to devices."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.fold_in(rng, 1), epoch), step)


def as_host_batches(batches):
    """Normalize any batch container to an indexable sequence of host
    device-array dicts. ``Plan`` is the primary input (DESIGN.md §8); raw
    ``PaddedBatch`` lists and ``BatchCache`` keep working as the back-compat
    shim. A ``Plan``/``BatchCache`` is consumed in place — reading batch i
    slices the contiguous cache, no per-batch dict materialization."""
    if isinstance(batches, Plan):
        return batches.cache
    if isinstance(batches, BatchCache):
        return batches
    return [b.device_arrays() if isinstance(b, PaddedBatch) else b
            for b in batches]


def _batch_labels(batches) -> List[np.ndarray]:
    """Per-batch real output labels, for the scheduler."""
    if isinstance(batches, Plan):
        return batches.batch_labels()
    if isinstance(batches, BatchCache):
        lab, msk = batches.fields["labels"], batches.fields["output_mask"]
        return [lab[i][msk[i] > 0] for i in range(len(batches))]
    return [b.labels[b.output_mask] for b in batches]


class GNNTrainer:
    def __init__(self, model_cfg: GNNConfig, optimizer: str = "adam",
                 lr: float = 1e-3, weight_decay: float = 0.0,
                 plateau_patience: int = 30, early_stop_patience: int = 100,
                 grad_accum: int = 1, seed: int = 0,
                 backend=None,
                 nonfinite_policy: str = "off"):
        # `backend` overrides model_cfg.backend (DESIGN.md §7/§14): a name,
        # "auto", or a BackendPolicy — one config can be A/B'd across
        # aggregation backends without rebuilding it, and the auto policy
        # dispatches per batch on the plan's stored autotuner decisions.
        model_cfg, self.policy = gnn_policy.resolve(model_cfg, backend)
        # NaN/Inf grad guard (DESIGN.md §12): "off" keeps the donated fast
        # path bit-identical; "skip" drops the poisoned update and keeps
        # going; "halt" raises NonFiniteGradError at the first bad step.
        if nonfinite_policy not in ("off", "skip", "halt"):
            raise ValueError(
                f"nonfinite_policy must be 'off', 'skip' or 'halt': "
                f"{nonfinite_policy!r}")
        self.cfg = model_cfg
        self.opt = get_optimizer(optimizer, weight_decay=weight_decay)
        self.sched = ReduceLROnPlateau(lr=lr, patience=plateau_patience)
        self.early_stop_patience = early_stop_patience
        self.grad_accum = grad_accum
        self.seed = seed
        self.nonfinite_policy = nonfinite_policy
        self.fault_stats = FaultStats("nonfinite_steps", "skipped_steps",
                                      "halts")
        # steps dispatched over every fit call: the ibmb/fit/step spans'
        # step_num
        self.step_num = 0
        self._step_cache: Dict = {}
        base = self._steps_for(self.cfg.backend,
                               int(getattr(self.cfg, "bcsr_block_f", 0)))
        # the fixed-decision executables (and the back-compat attribute
        # names); auto dispatch fetches per-decision sets via _steps_for
        self._train_step = base["train"]
        self._grad_step = base["grad"]
        self._eval_step = base["eval"]
        self._guarded_step = base["guarded"]
        self._apply_step = base["apply"]
        self._finite_check = base["finite"]

    def _steps_for(self, backend: str, block_f: int = 0) -> Dict:
        """Jit'd step set for one (backend, block_f) decision — traced once
        per distinct decision in play (DESIGN.md §14)."""
        key = (backend, int(block_f))
        if key not in self._step_cache:
            self._step_cache[key] = self._build_steps(
                gnn_policy.batch_config(self.cfg, backend, int(block_f)))
        return self._step_cache[key]

    def _build_steps(self, cfg) -> Dict:
        opt = self.opt

        def loss_fn(params, batch, rng):
            h = gnn_apply(cfg, params, batch, rng=rng, train=True)
            with jax.named_scope("loss"):
                logits = output_logits(h, batch)
                return masked_xent(logits, batch["labels"],
                                   batch["output_mask"])

        def update(grads, opt_state, params, lr):
            with jax.named_scope("optimizer"):
                updates, opt_state = opt.update(grads, opt_state, params, lr)
                return apply_updates(params, updates), opt_state

        @partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, batch, lr, rng):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
            params, opt_state = update(grads, opt_state, params, lr)
            return params, opt_state, loss

        @jax.jit
        def grad_step(params, batch, rng):
            return jax.value_and_grad(loss_fn)(params, batch, rng)

        def tree_finite(loss, grads):
            ok = jnp.isfinite(loss)
            for g in jax.tree_util.tree_leaves(grads):
                ok = ok & jnp.all(jnp.isfinite(g))
            return ok

        # Guarded variant (DESIGN.md §12): NO buffer donation — when the
        # step is non-finite the OLD params/opt_state are the output, so
        # they must stay live. jnp.where keeps the whole guard on-device;
        # the donated fast path above is untouched when the policy is off.
        @jax.jit
        def guarded_train_step(params, opt_state, batch, lr, rng):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
            ok = tree_finite(loss, grads)
            new_params, new_opt = update(grads, opt_state, params, lr)
            keep = lambda new, old: jnp.where(ok, new, old)
            return (jax.tree_util.tree_map(keep, new_params, params),
                    jax.tree_util.tree_map(keep, new_opt, opt_state),
                    loss, ok)

        finite_check = jax.jit(tree_finite)

        @partial(jax.jit, donate_argnums=(0, 1))
        def apply_step(params, opt_state, grads, lr):
            return update(grads, opt_state, params, lr)

        @jax.jit
        def eval_step(params, batch):
            h = gnn_apply(cfg, params, batch, train=False)
            with jax.named_scope("loss"):
                logits = output_logits(h, batch)
                loss = masked_xent(logits, batch["labels"],
                                   batch["output_mask"])
            acc_num = (logits.argmax(-1) == batch["labels"]).astype(jnp.float32) * batch["output_mask"]
            return loss * batch["output_mask"].sum(), acc_num.sum(), batch["output_mask"].sum()

        return {"train": train_step, "grad": grad_step, "apply": apply_step,
                "eval": eval_step, "guarded": guarded_train_step,
                "finite": finite_check}

    # ------------------------------------------------------------------
    def _on_nonfinite(self, ep: int, step: int) -> None:
        """Apply the nonfinite policy to one poisoned step (DESIGN.md §12)."""
        self.fault_stats.bump("nonfinite_steps")
        if self.nonfinite_policy == "halt":
            self.fault_stats.bump("halts")
            raise NonFiniteGradError(
                f"non-finite loss/gradients at epoch {ep} step {step} "
                f"(nonfinite_policy='halt')")
        self.fault_stats.bump("skipped_steps")

    def snapshot(self) -> Dict:
        """Degradation observability (DESIGN.md §12), the ServeStats idiom."""
        return {"nonfinite_policy": self.nonfinite_policy,
                "faults": self.fault_stats.snapshot()}

    # ------------------------------------------------------------------
    def evaluate(self, params, batches) -> Dict[str, float]:
        """Mini-batched evaluation. Accepts a Plan (primary), a BatchCache,
        a list of PaddedBatch, or a list of device-array dicts. Under an
        auto policy each batch runs the backend the plan's stored autotuner
        decision selects (DESIGN.md §14); decisions are read from the
        ORIGINAL container before cache normalization."""
        decisions = gnn_policy.batch_decisions(batches, self.policy, self.cfg)
        batches = as_host_batches(batches)
        tot_l = tot_a = tot_n = 0.0
        for i in range(len(batches)):
            l, a, n = self._steps_for(*decisions[i])["eval"](
                params, batches[i])
            tot_l += float(l); tot_a += float(a); tot_n += float(n)
        n = max(tot_n, 1.0)
        return {"loss": tot_l / n, "acc": tot_a / n}

    def fit(self,
            train_batches,                    # Plan | List[PaddedBatch] | Batcher
            val_batches,                      # Plan | List[PaddedBatch]
            num_classes: int,
            epochs: int = 100,
            schedule_mode: str = "tsp",
            eval_every: int = 1,
            verbose: bool = False,
            preprocess_time: float = 0.0,
            rng: Optional[jax.Array] = None,
            mesh=None) -> TrainResult:
        """Train on precomputed batches; with ``mesh`` the Plan executes
        data-parallel via ``repro.dist.data_parallel.ShardedPlanExecutor``
        (DESIGN.md §9): params replicate, each device takes one batch per
        super-step, gradients psum-mean — equivalent to single-device
        training with ``grad_accum = mesh_world(mesh)``."""
        base_rng = jax.random.PRNGKey(self.seed) if rng is None else rng
        # init from fold_in domain 0; dropout keys live in domain 1 keyed by
        # (epoch, step) — see `step_rng` for why the split is stateless.
        params = init_gnn(self.cfg, jax.random.fold_in(base_rng, 0))
        opt_state = self.opt.init(params)
        accum = GradAccumulator(self.grad_accum)

        if isinstance(train_batches, Plan) and not preprocess_time:
            # amortization accounting rides along in the artifact
            m = train_batches.meta
            preprocess_time = train_batches.timings.get(
                f"preprocess/{m.get('split')}/{m.get('mode')}", 0.0)
        fixed = isinstance(train_batches, (Plan, BatchCache, list, tuple))
        # (backend, block_f) per batch — the plan's stored autotuner
        # decisions under an auto policy, uniform otherwise — checked
        # against the batches' tiles before anything is traced (§14)
        if fixed:
            host = as_host_batches(train_batches)
            labels = _batch_labels(train_batches)
            order_fn = lambda ep: make_schedule(
                labels, num_classes, mode=schedule_mode, seed=self.seed + ep)
            decisions = gnn_policy.batch_decisions(
                train_batches, self.policy, self.cfg)
        val_host = as_host_batches(val_batches)
        val_decisions = gnn_policy.batch_decisions(
            val_batches, self.policy, self.cfg)

        executor = None
        if mesh is not None:
            if not fixed:
                raise ValueError(
                    "mesh execution needs precomputed fixed batches (a "
                    "Plan/BatchCache/list) — resampling batchers regenerate "
                    "per epoch and cannot be staged as super-steps")
            if self.grad_accum != 1:
                raise ValueError(
                    "mesh=... already averages gradients over each "
                    "super-step (DESIGN.md §9); combining it with "
                    "grad_accum is not supported")
            if self.nonfinite_policy != "off":
                raise ValueError(
                    "nonfinite_policy guards the single-device loop only; "
                    "the mesh super-step path is unguarded (DESIGN.md §12) "
                    "— use nonfinite_policy='off' with mesh=...")
            from repro.dist.data_parallel import ShardedPlanExecutor
            executor = ShardedPlanExecutor(mesh, self.cfg, self.opt,
                                           backend=self.policy)
            params = executor.replicate(params)
            opt_state = executor.replicate(opt_state)

        history: List[Dict] = []
        best_val_loss, best_val_acc, best_epoch = float("inf"), 0.0, -1
        # None until the first eval: the step functions donate `params`,
        # so holding the initial tree here would keep freed buffers
        best_params = None
        bad = 0
        epoch_times = []
        t_total0 = time.perf_counter()

        for ep in range(epochs):
            with TraceAnnotation("ibmb/fit/epoch", epoch=ep):
                t0 = time.perf_counter()
                if not fixed:  # resampling baselines pay regeneration every epoch
                    epoch_pb = train_batches.epoch_batches(ep)
                    host = as_host_batches(epoch_pb)
                    order = np.random.default_rng(self.seed + ep).permutation(len(host))
                    decisions = gnn_policy.batch_decisions(
                        epoch_pb, self.policy, self.cfg,
                        batcher=getattr(train_batches, "name",
                                        type(train_batches).__name__))
                else:
                    with TraceAnnotation("ibmb/fit/schedule"):
                        order = order_fn(ep)
                ep_loss = 0.0
                nsteps = 0
                if executor is not None:
                    # one shard_map super-step per `world` batches; micro-batch
                    # j of super-step s is global step s*world+j, so its dropout
                    # key matches the single-device loop's step counter exactly.
                    # The loader groups with the SAME superstep_indices the
                    # executor uses, so groups[si] names super-step si's batches
                    # and its (backend, block_f) executable (§14).
                    groups = executor.supersteps(order)
                    loader = PrefetchLoader(
                        host, order, group=executor.world,
                        device=executor.batch_sharding)
                    for si, (batch, w) in enumerate(loader):
                        with StepTraceAnnotation("ibmb/fit/step",
                                                 step_num=self.step_num):
                            self.step_num += 1
                            fns = executor.steps_for(*gnn_policy.superstep_decision(
                                decisions, groups[si][0]))
                            keys = jnp.stack(
                                [step_rng(base_rng, ep, si * executor.world + j)
                                 for j in range(executor.world)])
                            params, opt_state, losses = fns.train(
                                params, opt_state, batch, w,
                                jnp.float32(self.sched.lr), keys)
                            real = np.asarray(w) > 0
                            with TraceAnnotation("ibmb/fit/loss_sync"):
                                ep_loss += float(np.asarray(losses)[real].sum())
                            nsteps += int(real.sum())
                else:
                    loader = PrefetchLoader(host, order)
                    for bi, batch in enumerate(loader):
                        with StepTraceAnnotation("ibmb/fit/step",
                                                 step_num=self.step_num):
                            self.step_num += 1
                            # loader position bi holds batch order[bi]; its stored
                            # decision picks the executable (uniform when fixed)
                            steps = self._steps_for(*decisions[int(order[bi])])
                            sub = step_rng(base_rng, ep, bi)
                            if self.grad_accum == 1:
                                if self.nonfinite_policy == "off":
                                    params, opt_state, loss = steps["train"](
                                        params, opt_state, batch,
                                        jnp.float32(self.sched.lr), sub)
                                else:
                                    params, opt_state, loss, ok = steps["guarded"](
                                        params, opt_state, batch,
                                        jnp.float32(self.sched.lr), sub)
                                    with TraceAnnotation("ibmb/fit/loss_sync"):
                                        finite = bool(ok)
                                    if not finite:
                                        self._on_nonfinite(ep, bi)
                                        continue   # loss is poisoned; update held
                            else:
                                loss, grads = steps["grad"](params, batch, sub)
                                if self.nonfinite_policy != "off":
                                    with TraceAnnotation("ibmb/fit/loss_sync"):
                                        finite = bool(
                                            self._finite_check(loss, grads))
                                    if not finite:
                                        # never let a NaN enter the accumulator:
                                        # one bad micro-batch would poison the
                                        # whole macro-step
                                        self._on_nonfinite(ep, bi)
                                        continue
                                g = accum.add(grads)
                                if g is not None:
                                    params, opt_state = self._apply_step(
                                        params, opt_state, g, jnp.float32(self.sched.lr))
                            with TraceAnnotation("ibmb/fit/loss_sync"):
                                ep_loss += float(loss)
                            nsteps += 1
                    if self.grad_accum > 1:
                        g = accum.flush()
                        if g is not None:
                            params, opt_state = self._apply_step(
                                params, opt_state, g, jnp.float32(self.sched.lr))
                epoch_times.append(time.perf_counter() - t0)

                if (ep + 1) % eval_every == 0:
                    with TraceAnnotation("ibmb/fit/eval"):
                        val = executor.evaluate(params, val_host,
                                                decisions=val_decisions) \
                            if executor is not None \
                            else self.evaluate(params, val_batches)
                    self.sched.step(val["loss"])
                    history.append({"epoch": ep, "train_loss": ep_loss / max(nsteps, 1),
                                    "val_loss": val["loss"], "val_acc": val["acc"],
                                    "lr": self.sched.lr,
                                    "time": time.perf_counter() - t_total0})
                    if verbose:
                        print(f"  ep {ep:4d} loss {ep_loss/max(nsteps,1):.4f} "
                              f"val_loss {val['loss']:.4f} val_acc {val['acc']:.4f} lr {self.sched.lr:.2e}")
                    if val["loss"] < best_val_loss - 1e-6:
                        best_val_loss, best_val_acc, best_epoch = val["loss"], val["acc"], ep
                        best_params = jax.tree_util.tree_map(lambda x: x.copy(), params)
                        bad = 0
                    else:
                        bad += 1
                        if bad >= self.early_stop_patience:
                            break
        return TrainResult(
            params=params if best_params is None else best_params,
            history=history, best_val_acc=best_val_acc,
            best_epoch=best_epoch,
            time_per_epoch=float(np.mean(epoch_times)) if epoch_times else 0.0,
            preprocess_time=preprocess_time,
            total_time=time.perf_counter() - t_total0)
