"""Training: the window is one ``GNNTrainer.fit`` call over the train plan,
with Adam and no evaluation inside it.

Set-up builds the trainer and drives it through one epoch with the same
call, plan and loader as the window. A tap on the trainer's train step
keeps what the reference needs of that epoch's first steps: the initial
parameters, which batch each step staged, each step's loss, the optimizer
state after the first step and the parameters after the third. The epoch
also compiles every step executable and sizes the window: ``ceil(seconds /
epoch time)`` epochs, timed by a second warm-up epoch.

``train_nodes_per_s`` is every real labelled output node trained over the
window's whole time, which ends when the final parameters are ready.
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

from ibmb_bench import data, flops, harness
from ibmb_bench.reference import common, graph as ref_graph

NO_EVAL = 1 << 30          # eval_every past the last epoch: no evaluation
COMPARED_STEPS = 3
B1 = 0.9                   # Adam's first-moment decay, as configured


class StepTap:
    """Wraps the trainer's jitted train step for the next ``fit`` call and
    copies to the host what the first ``steps`` steps took and gave.

    The step donates its parameters and optimizer state, so each is copied
    before the next call can consume it.
    """

    def __init__(self, trainer, steps: int):
        self.trainer, self.steps = trainer, steps
        self.calls = 0
        self.losses, self.staged = [], []
        self.params0 = self.params_n = self.opt1 = None

    def __enter__(self):
        orig = self.trainer._steps_for

        def steps_for(backend, block_f=0):
            s = orig(backend, block_f)
            return {**s, "train": self._wrap(s["train"])}

        self.trainer._steps_for = steps_for
        return self

    def __exit__(self, *exc):
        del self.trainer._steps_for

    def _wrap(self, fn):
        def call(params, opt_state, batch, lr, rng):
            k = self.calls
            self.calls += 1
            if k == 0:
                self.params0 = common.to_host(params)
            if k == self.steps:
                self.params_n = common.to_host(params)
            if k < self.steps:
                self.staged.append({f: np.asarray(batch[f])
                                    for f in ("output_idx", "labels")})
            out = fn(params, opt_state, batch, lr, rng)
            if k == 0:
                self.opt1 = common.to_host(out[1])
            if k < self.steps:
                self.losses.append(float(out[2]))
            return out
        return call


def _batch_index(plan, staged) -> int:
    """Which plan batch a step staged, by its output rows and labels."""
    f = plan.cache.fields
    hit = np.nonzero(
        (f["output_idx"] == staged["output_idx"][None]).all(axis=1)
        & (f["labels"] == staged["labels"][None]).all(axis=1))[0]
    return int(hit[0]) if len(hit) else -1


def trainer_for(ctx, plan, num_classes: int, seed: int):
    """The trainer of ``seed``, driven through one epoch with the tap on.
    Returns the trainer, its ``fit(epochs)``, the run's key and the tap."""
    from repro.train import GNNTrainer
    opt = ctx.config["optimizer"]
    trainer = GNNTrainer(data.model_config(ctx), optimizer=opt["name"],
                         lr=opt["lr"], seed=seed, backend=data.policy(ctx))
    key = harness.seed_key(seed)

    def fit(epochs):
        res = trainer.fit(plan, plan, num_classes, epochs=epochs,
                          eval_every=NO_EVAL, rng=key)
        return jax.block_until_ready(res.params)

    with StepTap(trainer, COMPARED_STEPS) as tap:
        fit(1)
    return trainer, fit, key, tap


def load(ctx):
    g = data.graph(ctx)
    ds = data.program_dataset(ctx, g)
    data.check_model(ctx, ds)
    return g, ds, data.plan(ctx, ds, "train", for_inference=False)


def setup(ctx):
    g, ds, plan = load(ctx)
    trainer, fit, key, tap = trainer_for(ctx, plan, ds.num_classes, ctx.seed)
    t0 = time.perf_counter()
    fit(1)
    epoch_s = time.perf_counter() - t0
    epochs = max(1, math.ceil(ctx.seconds / epoch_s))
    nodes, edges = flops.real_counts(plan.cache.fields)
    outputs = int((plan.cache.fields["output_mask"] > 0).sum())
    step_flops = sum(flops.train_step_flops(ctx.model, int(n), int(e))
                     for n, e in zip(nodes, edges))
    ctx.log(f"plan: {len(plan)} batches of {plan.cache.fields['features'].shape[1]} "
            f"nodes, {plan.cache.nbytes() / 2**30:.3f} GiB, decisions "
            f"{plan.batch_backends()}; warm epoch {epoch_s:.4f}s -> "
            f"{epochs} epochs")
    return dict(g=g, plan=plan, trainer=trainer, fit=fit, key=key, tap=tap,
                epochs=epochs, outputs=outputs, epoch_flops=step_flops)


def window(ctx, st):
    epochs = st["epochs"]
    t0 = time.perf_counter()
    with ctx.span("bench/fit"):
        params = st["fit"](epochs)
    dt = time.perf_counter() - t0
    steps = epochs * len(st["plan"])
    finite = all(np.isfinite(np.asarray(x)).all()
                 for x in jax.tree_util.tree_leaves(params))
    return dict(metrics={"train_nodes_per_s": epochs * st["outputs"] / dt},
                attempted=steps, failed=0 if finite else steps,
                counters=dict(steps=steps, epochs=epochs),
                flops=epochs * st["epoch_flops"])


def worst_leaf_gap(prog: list, ref: list, keep: list) -> float:
    """The largest gap between the program's and the reference's norm of a
    leaf, over the reference's norm of that leaf or of the median leaf,
    whichever is larger; leaves where ``keep`` is False are left out."""
    pn = [float(np.linalg.norm(p)) for p in prog]
    rn = [float(np.linalg.norm(r)) for r in ref]
    med = float(np.median(rn))
    return max(abs(p - r) / max(r, med, 1e-30)
               for p, r, k in zip(pn, rn, keep) if k)


def _init(init_params, model_key, key):
    return init_params(dict(model_key), key)


def compare(model, tap, plan, g, key, lr, candidate=None, half=False):
    """The numbers that decide ``correct``: the program's first steps
    against the reference's. With ``candidate`` (a dtype) the reference in
    that precision takes the program's place, as the control does; with
    ``half`` as well, its loss leaves out half of each batch's outputs (a
    planted fault)."""
    from ibmb_bench.reference import gcn, gat
    ref = {"gcn": gcn, "gat": gat}[model["kind"]]
    ng = ref_graph.normalized_graph(g.src, g.dst, g.num_nodes)
    idx = [_batch_index(plan, s) for s in tap.staged]
    if min(idx) < 0 or len(set(idx)) < len(idx):
        return dict(loss_gap=float("inf"), grad_norm_gap=float("inf"),
                    update_gap=float("inf"))
    batches = [data.reference_batch(g, ng, plan, b) for b in idx]
    keys = [common.dropout_key(key, 0, s) for s in range(len(batches))]
    p0 = common.jitted(_init, ref.init_params, tuple(sorted(model.items())))(
        jax.random.fold_in(key, 0))
    mkey = tuple(sorted(model.items()))
    r_losses, r_g1, r_p = common.train_steps(ref.loss, mkey, p0, batches,
                                             keys, lr, common.F32)
    if candidate is None:
        c_losses = tap.losses
        c_g1 = jax.tree_util.tree_map(lambda m: m / (1 - B1), tap.opt1["m"])
        c_p, c_p0 = tap.params_n, tap.params0
    else:
        cb = batches
        if half:
            cb = [dict(b, out=b["out"][: len(b["out"]) // 2],
                       labels=b["labels"][: len(b["out"]) // 2])
                  for b in batches]
        c_losses, c_g1, c_p = common.train_steps(ref.loss, mkey, p0, cb,
                                                 keys, lr, candidate)
        c_p0 = common.to_host(common.cast(p0, candidate))
    leaves = jax.tree_util.tree_leaves
    r_g = leaves(r_g1)
    gmed = float(np.median([np.linalg.norm(x) for x in r_g]))
    keep = [float(np.linalg.norm(x)) >= 1e-3 * gmed for x in r_g]
    p0h = leaves(common.to_host(p0))
    r_d = [a - b for a, b in zip(leaves(r_p), p0h)]
    c_d = [a - b for a, b in zip(leaves(c_p), leaves(c_p0))]
    loss_gap = max(abs(c - r) / abs(r) for c, r in zip(c_losses, r_losses))
    return dict(loss_gap=float(loss_gap),
                grad_norm_gap=worst_leaf_gap(leaves(c_g1), r_g, keep),
                update_gap=worst_leaf_gap(c_d, r_d, keep),
                excluded_leaves=int(len(keep) - sum(keep)),
                ref_losses=r_losses, losses=list(c_losses), batches=idx)


def check(ctx, st):
    st.pop("trainer")
    st.pop("fit")
    got = compare(ctx.model, st["tap"], st["plan"], st["g"], st["key"],
                  ctx.config["optimizer"]["lr"])
    ctx.log(f"losses {got.get('losses')} reference {got.get('ref_losses')} "
            f"batches {got.get('batches')} excluded leaves "
            f"{got.get('excluded_leaves')} grad_norm_gap (not compared) "
            f"{got.get('grad_norm_gap')}")
    limits = ctx.workload["limits"]
    return [dict(name=n, value=float(got[n]), limit=float(limits[n]))
            for n in sorted(limits)]
