"""BackendPolicy: the one place that maps batches to aggregation backends
and checks them (DESIGN.md §14).

A policy is one of:

* ``BackendPolicy.fixed("segment" | "bcsr" | "dense")`` — every batch runs
  the named backend;
* ``BackendPolicy.auto()`` — each batch runs the backend the plan-build
  autotuner decided for it (``Plan.batch_backends``, from the tile-fill and
  degree stats recorded during preprocessing — ``repro.core.autotune``); a
  container that stores no decisions dispatches by tile presence.

``GNNTrainer``, ``GNNInferenceEngine`` and ``ShardedPlanExecutor`` take a
``backend=`` override (``None``, a backend name, ``"auto"`` or a policy)
and call :func:`resolve` once. Each then gets its per-batch
``(backend, block_f)`` list, tile check included, from one call to
:func:`batch_decisions`, and jits one executable per distinct decision
through :func:`batch_config` — static shapes per backend, so auto dispatch
never recompiles beyond one executable per decision in play.
``GNNConfig.backend`` is that per-executable choice, one of
``ops.BACKENDS``; per-batch dispatch is a policy, never a config value.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple, Union

from repro.models.gnn import ops as gnn_ops


@dataclasses.dataclass(frozen=True)
class BackendPolicy:
    """How batches map to aggregation backends: ``fixed(name)`` or ``auto``."""
    mode: str                               # "fixed" | "auto"
    backend: Optional[str] = None           # fixed mode only

    @classmethod
    def fixed(cls, name: str) -> "BackendPolicy":
        return cls("fixed", gnn_ops.check_backend(name))

    @classmethod
    def auto(cls) -> "BackendPolicy":
        return cls("auto")

    @property
    def is_auto(self) -> bool:
        return self.mode == "auto"


BackendSpec = Union[None, str, BackendPolicy]


def as_policy(spec: BackendSpec) -> Optional[BackendPolicy]:
    """Normalize a ``None | str | BackendPolicy`` override. ``"auto"``
    (string) means the auto policy; other strings are fixed backends."""
    if spec is None or isinstance(spec, BackendPolicy):
        return spec
    if isinstance(spec, str):
        return BackendPolicy.auto() if spec == "auto" \
            else BackendPolicy.fixed(spec)
    raise TypeError(
        f"backend must be None, a backend name, 'auto', or a BackendPolicy "
        f"— got {type(spec).__name__}")


def resolve(model_cfg, backend: BackendSpec = None):
    """``(model_cfg, policy)`` for one consumer's ``backend`` override.

    An explicit ``backend`` wins over ``model_cfg.backend``, which must be
    one of ``ops.BACKENDS``: a config naming ``"auto"`` or an unknown
    backend raises ``ValueError``. For a fixed policy the returned config's
    ``backend`` is the fixed name; for auto it is the ``"segment"`` base
    (always executable — every batch carries COO edges), and consumers
    derive per-batch configs via :func:`batch_config`.
    """
    name = gnn_ops.check_backend(getattr(model_cfg, "backend", "segment"))
    pol = as_policy(backend) or BackendPolicy.fixed(name)
    base = "segment" if pol.is_auto else pol.backend
    if name != base:
        model_cfg = dataclasses.replace(model_cfg, backend=base)
    return model_cfg, pol


def batch_config(model_cfg, backend: str, block_f: int = 0):
    """The per-executable config for one (backend, tuned block_f) decision —
    consumers jit one forward per distinct config, picked host-side."""
    if getattr(model_cfg, "backend", None) == backend \
            and int(getattr(model_cfg, "bcsr_block_f", 0)) == int(block_f):
        return model_cfg
    return dataclasses.replace(model_cfg, backend=backend,
                               bcsr_block_f=int(block_f))


def _has_tiles(batch) -> bool:
    if hasattr(batch, "has_bcsr"):
        return bool(batch.has_bcsr)
    return "tile_cols" in batch and "tile_vals" in batch


def _decide(host, policy: BackendPolicy, model_cfg) -> List[Tuple[str, int]]:
    n = len(host)
    bf = int(getattr(model_cfg, "bcsr_block_f", 0))
    if not policy.is_auto:
        return [(policy.backend, bf)] * n
    if getattr(model_cfg, "kind", "gcn") == "gat":
        return [("segment", 0)] * n
    names = getattr(host, "batch_backends", None)
    if callable(names):                      # Plan v3 (or v2 fallback)
        tuned = host.batch_block_fs()
        return [(str(b), int(t)) for b, t in zip(names(), tuned)]
    cache = getattr(host, "cache", None)     # Plan-like wrapper
    if cache is not None and host is not cache:
        return _decide(cache, policy, model_cfg)
    return [("bcsr", bf) if _has_tiles(host[i]) else ("segment", 0)
            for i in range(n)]


def batch_decisions(host, policy: BackendPolicy, model_cfg,
                    batcher: Optional[str] = None) -> List[Tuple[str, int]]:
    """Per-batch ``(backend, block_f)`` execution decisions for `host`,
    checked against its batches.

    `host` is anything the trainer, engine or executor runs: a ``Plan``
    (carries the autotuner's v3 decisions), a ``BatchCache``/
    ``LazyBatchCache``, or a plain sequence of batch dicts /
    ``PaddedBatch``. Fixed policies return a uniform list; the auto policy
    reads the plan's stored decisions and dispatches containers without
    them by tile presence. GAT has no precomputable tiles, so auto always
    resolves it to segment, and it needs no tiles under any policy.

    Raises ``ValueError`` naming bcsr, before anything is traced, when a
    bcsr decision falls on a batch without tiles. `batcher` names the
    resampling batcher that generated `host`, for the message.
    """
    decisions = _decide(host, policy, model_cfg)
    if getattr(model_cfg, "kind", "gcn") == "gat" \
            or all(b != "bcsr" for b, _ in decisions):
        return decisions
    cache = getattr(host, "cache", host)
    fields = getattr(cache, "fields", None)  # one field set for every batch
    for i, (b, _) in enumerate(decisions):
        if b == "bcsr" and not _has_tiles(
                fields if fields is not None else cache[i]):
            if batcher is not None:
                raise ValueError(
                    f"backend='bcsr' needs batches with precomputed BCSR "
                    f"tiles, but batcher {batcher!r} (graph/sampling.py) "
                    f"regenerates batches per epoch without tiles. Train "
                    f"from an IBMBPipeline plan built with "
                    f"IBMBConfig(backend='bcsr'), or use backend='segment' "
                    f"for this batcher (DESIGN.md §7).")
            raise ValueError(
                f"backend='bcsr' is decided for batch {i}, which has no "
                f"tile_cols/tile_vals — build batches with bcsr_block set "
                f"(IBMBConfig(backend='bcsr') or "
                f"build_batches(bcsr_block=128)), or use backend='segment' "
                f"(DESIGN.md §7)")
    return decisions


def superstep_decision(decisions: Sequence[Tuple[str, int]],
                       idx) -> Tuple[str, int]:
    """One decision for a shard_map super-step: its members execute in a
    single jitted body, so they must share a backend. Uniform groups keep
    their decision; mixed groups fall back to segment (always executable —
    the schedule groups consecutive batches, and the autotuner's decisions
    are strongly run-length-uniform in practice, so this is the rare tail).
    """
    got = {decisions[int(i)] for i in idx}
    if len(got) == 1:
        return next(iter(got))
    backends = {b for b, _ in got}
    if len(backends) == 1:                   # same backend, mixed block_f
        return (next(iter(backends)), 0)
    return ("segment", 0)
