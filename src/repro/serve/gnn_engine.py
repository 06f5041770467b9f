"""Request-level GNN inference over a frozen Plan (DESIGN.md §8).

The paper's 130x inference speedup comes from precomputed batches; this
engine turns that into a *serving* story: queries are arbitrary sets of
output-node ids (think: "score these users"), answered from a
``Plan.load``-ed artifact with NO preprocessing on the request path.

Dispatch, per query:

1. **Route** — the plan's routing index maps every queried node id to its
   precomputed ``(batch, row)`` in O(log M) per id (binary search over the
   sorted output-node table).
2. **Coalesce** — requests in flight that hit the same precomputed batch
   share ONE forward pass (``run``), the GNN analogue of ``ServeEngine``'s
   slot-based continuous batching: the unit of execution is the batch, the
   unit of admission is the request.
3. **Execute** — one jit'd forward per (backend, block_f) decision. The
   backend override is a :class:`~repro.models.gnn.policy.BackendPolicy`
   (or a plain name): fixed policies run every batch on one backend;
   ``BackendPolicy.auto()`` dispatches each batch on the plan's stored
   autotuner decision (DESIGN.md §14). Static shapes ⇒ one executable per
   distinct decision, never recompiled. With ``mesh=...`` the misses
   additionally coalesce ACROSS DEVICES: one batch per device per
   shard_map super-step (DESIGN.md §9), so a cold burst's latency
   amortizes over the mesh.
4. **Gather** — per-node logit rows are sliced out of the batch output and
   scattered back into each request.

Repeat traffic is served from an LRU of recent batch *outputs* — hot
batches answer from host memory without touching the accelerator.

Profiler spans (``jax.profiler.TraceAnnotation``, free while no trace is
taken): ``ibmb/engine/route`` (routing lookup), ``ibmb/engine/forward``
(enqueueing one batch's or super-step's transfer and forward),
``ibmb/engine/fetch`` (device wait + copy of its logits to the host) and
``ibmb/engine/scatter`` (rows copied into a query's answer).

Dynamic graphs (DESIGN.md §10): ``swap(plan, delta)`` hot-swaps the engine
onto a refreshed plan atomically between requests. Only the LRU entries of
batches the refresh rebuilt or patched are invalidated; untouched batches
keep serving from cache, and the per-``versions`` stats table (requests /
lru_hits / batch_runs / hit_rate per plan version) is the observable proof
that traffic kept flowing across the swap.

The engine is single-threaded: "concurrent" means requests admitted into
one ``run`` call, which coalesces them. The async serving tier
(``repro.serve.async_engine.AsyncGNNEngine``, DESIGN.md §11) is the
multi-threaded front: it owns one engine per tenant, accumulates a live
request stream into micro-batching windows, and serializes every ``run``
and ``swap`` behind a per-tenant lock.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.plan import Plan
from repro.serve.common import SystemClock
from repro.models.gnn import policy as gnn_policy
from repro.models.gnn.models import GNNConfig, gnn_apply, output_logits


@dataclasses.dataclass
class GNNRequest:
    """One inference request: logits for an arbitrary set of node ids."""
    node_ids: np.ndarray
    logits: Optional[np.ndarray] = None     # (len(node_ids), C) when done
    latency_s: Optional[float] = None
    done: bool = False
    error: Optional[str] = None             # set instead of done on bad ids


class GNNInferenceEngine:
    """Serve per-node GNN predictions from a frozen ``Plan``.

    ``query`` answers one request synchronously; ``run`` drains a list of
    requests, coalescing all requests that touch the same precomputed batch
    into one forward pass. Per-batch output logits are LRU-cached
    (``cache_batches`` entries) so repeat traffic skips the forward
    entirely. The engine never re-preprocesses: everything it needs is in
    the plan (DESIGN.md §8).
    """

    def __init__(self, plan: Plan, model_cfg: GNNConfig, params,
                 backend=None, cache_batches: int = 8,
                 mesh=None, clock=None):
        # `backend` is a name, "auto", or a BackendPolicy (DESIGN.md §14)
        model_cfg, self.policy = gnn_policy.resolve(model_cfg, backend)
        self.plan = plan
        self.cfg = model_cfg
        self.params = params
        # request-latency timing through the injectable clock (DESIGN.md
        # §11) — FakeClock tests can observe deterministic latencies
        self.clock = clock if clock is not None else SystemClock()
        self.cache_batches = max(0, cache_batches)
        # per-batch (backend, block_f): the plan's stored autotuner
        # decisions under an auto policy, uniform under a fixed one —
        # checked against the plan's tiles at construction, not on the
        # first unlucky query
        self._decisions = gnn_policy.batch_decisions(plan, self.policy,
                                                     model_cfg)
        self._lru: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self.stats: Dict = dict(
            requests=0, nodes=0, batch_runs=0, lru_hits=0, supersteps=0,
            evictions=0, swap_count=0, swap_rollbacks=0, versions={})
        # audit trail of swap attempts (DESIGN.md §12): one record per call,
        # including refused swaps that rolled back to the parent version
        self.swap_audit: List[Dict] = []
        self._vstats = self._version_bucket(getattr(plan, "version", 0))

        # mesh serving (DESIGN.md §9): concurrent requests coalesce ACROSS
        # devices — missing batches are grouped one-per-device and answered
        # by a single shard_map forward per super-step. Every backend runs
        # under shard_map (bcsr uses the compiled streaming SpMM off-TPU,
        # the fused Pallas kernel on TPU — DESIGN.md §14).
        self._ex = None
        if mesh is not None:
            from repro.dist.data_parallel import ShardedPlanExecutor
            self._ex = ShardedPlanExecutor(mesh, model_cfg,
                                           backend=self.policy)
            self.params = self._ex.replicate(params)

        # one jit'd forward per (backend, block_f) decision, built lazily;
        # `_forward` holds the base decision's executable as a plain
        # attribute (the patchable surface tests inject faults through)
        self._fwd: Dict = {}
        self._base_key = (model_cfg.backend,
                          int(getattr(model_cfg, "bcsr_block_f", 0)))
        self._forward = self._build_forward(*self._base_key)

    def _build_forward(self, backend: str, block_f: int):
        cfg = gnn_policy.batch_config(self.cfg, backend, block_f)

        @jax.jit
        def _forward(params, batch):
            h = gnn_apply(cfg, params, batch, train=False)
            return output_logits(h, batch)          # (max_outputs, C)

        return _forward

    def _forward_for(self, backend: str, block_f: int = 0):
        """The per-batch forward executable for one (backend, block_f)
        decision — traced once per distinct decision in play (§14). The
        base decision answers through the ``_forward`` attribute so a
        patched attribute (fault injection) is honoured."""
        key = (backend, int(block_f))
        if key == self._base_key:
            return self._forward
        if key not in self._fwd:
            self._fwd[key] = self._build_forward(backend, int(block_f))
        return self._fwd[key]

    # ----------------------------------------------------------- hot swap
    def swap(self, plan: Plan, delta=None, validate: bool = True
             ) -> Dict[str, int]:
        """Hot-swap onto a refreshed plan (DESIGN.md §10), atomically
        between requests (the engine is single-threaded, so "atomic" means
        no query ever observes a half-updated plan/LRU pair: everything is
        computed first, then assigned).

        ``delta`` is the :class:`~repro.core.update.PlanDelta` audit record
        from ``IBMBPipeline.refresh``: only its rebuilt/patched batches are
        dropped from the LRU — untouched batches keep serving from cache,
        which is the zero-downtime property the per-``versions`` stats
        prove. Without a delta the whole LRU is cleared conservatively; a
        delta that does not link the SERVING plan to the INCOMING plan
        (parent/child fingerprint mismatch) is refused with ValueError
        before any serving state changes — a mismatched (plan, audit) pair
        would silently keep stale logits cached.

        Graceful degradation (DESIGN.md §12): with ``validate=True`` the
        incoming plan's routing invariants are checked
        (:func:`repro.core.plan.check_routing`) on top of the backend and
        audit checks, so a corrupt or hand-damaged plan is refused. ANY
        failure rolls the engine back to the plan it was serving — the
        stale-but-correct parent version keeps answering bit-identically —
        and appends a rollback record to ``swap_audit`` before the error
        propagates. Returns ``{"invalidated": ..., "kept": ...}``.
        """
        prev = (self.plan, self._lru, self._vstats, self._decisions)
        try:
            # fail fast, BEFORE touching any serving state: the incoming
            # plan carries its OWN autotuner decisions (a refresh may
            # re-decide rebuilt batches, DESIGN.md §14)
            decisions = gnn_policy.batch_decisions(plan, self.policy,
                                                   self.cfg)
            if delta is not None:
                if delta.parent_fingerprint != self.plan.fingerprint:
                    raise ValueError(
                        f"swap: delta parents {delta.parent_fingerprint!r} "
                        f"but the engine is serving "
                        f"{self.plan.fingerprint!r} — refresh the serving "
                        f"plan, not another chain")
                if delta.child_fingerprint != plan.fingerprint:
                    raise ValueError(
                        f"swap: delta produced {delta.child_fingerprint!r} "
                        f"but the incoming plan is {plan.fingerprint!r} — "
                        f"this audit record does not describe that plan, "
                        f"and trusting it would keep stale LRU entries "
                        f"serving")
            if validate:
                from repro.core.plan import check_routing
                check_routing(plan)
            if delta is None:
                dirty = set(self._lru)              # conservative: drop all
            else:
                dirty = set(int(i) for i in delta.dirty)
            keep = OrderedDict((bi, out) for bi, out in self._lru.items()
                               if bi not in dirty and bi < len(plan))
            invalidated = len(self._lru) - len(keep)
            # the actual swap: plan (with routing index) + LRU + per-batch
            # decisions move together
            self.plan, self._lru, self._decisions = plan, keep, decisions
            self.stats["swap_count"] += 1
            self.stats["evictions"] += invalidated
            self._vstats = self._version_bucket(getattr(plan, "version", 0))
        except Exception as e:
            # roll back (defensively — validation failures precede any
            # mutation) and audit: the tenant keeps serving the parent
            self.plan, self._lru, self._vstats, self._decisions = prev
            self.stats["swap_rollbacks"] += 1
            self.swap_audit.append(dict(
                ok=False, serving_version=getattr(self.plan, "version", 0),
                refused_version=getattr(plan, "version", None),
                reason=f"{type(e).__name__}: {e}"))
            raise
        self.swap_audit.append(dict(
            ok=True, from_version=getattr(prev[0], "version", 0),
            to_version=getattr(plan, "version", 0),
            invalidated=invalidated, kept=len(keep)))
        return {"invalidated": invalidated, "kept": len(keep)}

    def ooc_stats(self) -> Optional[Dict]:
        """Resident-budget/IO counters of an out-of-core plan's lazy cache
        (DESIGN.md §13), or ``None`` for a resident plan — the engine-level
        hook the serving tier's ``snapshot`` surfaces so operators can see
        batch faulting, eviction pressure, and retried reads per tenant."""
        snap = getattr(self.plan.cache, "snapshot", None)
        return snap() if callable(snap) else None

    # ------------------------------------------------------------ internals
    def _version_bucket(self, version: int) -> Dict[str, float]:
        """Per-plan-version counters inside ``stats['versions']`` — the
        hot-swap observability surface (DESIGN.md §10)."""
        return self.stats["versions"].setdefault(
            int(version), dict(requests=0, lru_hits=0, batch_runs=0,
                               hit_rate=0.0))

    def _bump(self, **inc) -> None:
        for k, v in inc.items():
            self.stats[k] += v
            if k in self._vstats:
                self._vstats[k] += v
        served = self._vstats["lru_hits"] + self._vstats["batch_runs"]
        if served:
            self._vstats["hit_rate"] = self._vstats["lru_hits"] / served

    def _lru_put(self, bi: int, out: np.ndarray) -> np.ndarray:
        self._bump(batch_runs=1)
        if self.cache_batches:
            self._lru[bi] = out
            while len(self._lru) > self.cache_batches:
                self._lru.popitem(last=False)
                self.stats["evictions"] += 1
        return out

    def _flush_misses(self, missing):
        """Compute the logits of `missing` (≤ world batches), yielding
        (bi, logits). A lone miss skips the super-step machinery — padding
        it to `world` identical copies would waste world−1 devices' staging
        and compute — and runs the plain per-batch forward instead (the
        replicated params commit the computation to the mesh either way)."""
        if len(missing) == 1 or self._ex is None:
            for bi in missing:
                fwd = self._forward_for(*self._decisions[bi])
                # the call enqueues the host batch's transfer and the
                # forward; the fetch waits for both and copies back
                with TraceAnnotation("ibmb/engine/forward", batch=bi):
                    lg = fwd(self.params, self.plan.cache[bi])
                with TraceAnnotation("ibmb/engine/fetch", batch=bi):
                    lg = np.asarray(lg)
                yield bi, self._lru_put(bi, lg)
            return
        from repro.dist.data_parallel import superstep_indices
        (idx, w), = superstep_indices(np.asarray(missing), self._ex.world)
        fns = self._ex.steps_for(
            *gnn_policy.superstep_decision(self._decisions, idx))
        first = int(idx[0])
        with TraceAnnotation("ibmb/engine/forward", batch=first):
            batch, _w = self._ex.stage(self.plan.cache, idx, w)
            lg = fns.forward(self.params, batch)
        with TraceAnnotation("ibmb/engine/fetch", batch=first):
            lg = np.asarray(lg)
        self.stats["supersteps"] += 1
        for j in range(len(idx)):
            if w[j] > 0:
                yield int(idx[j]), self._lru_put(int(idx[j]), lg[j])

    def _iter_logits(self, need):
        """Yield (bi, output-row logits) for every batch index in `need`,
        through the LRU. Misses run coalesced — one batch per device per
        shard_map super-step when a mesh is configured — but are flushed
        chunk by chunk, so peak host memory beyond the LRU stays at
        O(world) batch outputs however many batches a request set touches
        (the caller scatters each batch's rows and drops the reference)."""
        world = self._ex.world if self._ex is not None else 1
        missing: List[int] = []
        for bi in need:
            bi = int(bi)
            if bi in self._lru:
                self._lru.move_to_end(bi)
                self._bump(lru_hits=1)
                yield bi, self._lru[bi]
                continue
            missing.append(bi)
            if len(missing) == world:
                yield from self._flush_misses(missing)
                missing = []
        if missing:
            yield from self._flush_misses(missing)

    def _batch_logits(self, bi: int) -> np.ndarray:
        """Output-row logits of precomputed batch `bi`, through the LRU."""
        return dict(self._iter_logits([bi]))[int(bi)]

    # -------------------------------------------------------------- queries
    def query(self, node_ids: Sequence[int]) -> np.ndarray:
        """Logits for `node_ids` (any output nodes covered by the plan),
        in query order. Raises KeyError for ids the plan does not cover."""
        q = np.asarray(node_ids, dtype=np.int64).ravel()
        with TraceAnnotation("ibmb/engine/route"):
            bidx, rows = self.plan.routing.lookup(q)
            need = np.unique(bidx)
        self._bump(requests=1, nodes=len(q))
        out = None
        for bi, lg in self._iter_logits(need):
            with TraceAnnotation("ibmb/engine/scatter", batch=int(bi)):
                if out is None:
                    out = np.empty((len(q), lg.shape[1]), lg.dtype)
                sel = bidx == bi
                out[sel] = lg[rows[sel]]
        if out is None:                              # empty query
            out = np.zeros((0, self.cfg.out_dim), np.float32)
        return out

    def run(self, requests: List[GNNRequest]) -> Dict[str, float]:
        """Drain `requests`, coalescing across them: every precomputed batch
        needed by ANY request runs at most once (then serves them all).
        Records per-request latency (admission → completion). A request with
        ids the plan does not cover gets its `error` set and is skipped —
        it never denies service to the rest of the coalesced set."""
        t0 = self.clock.now()
        routed = []
        for req in requests:
            q = np.asarray(req.node_ids, dtype=np.int64).ravel()
            try:
                bidx, rows = self.plan.routing.lookup(q)
            except KeyError as e:
                req.error = str(e)
                req.done, req.logits = False, None
                continue
            req.logits = None
            routed.append((req, q, bidx, rows))
            self._bump(requests=1, nodes=len(q))
        # batch → list of (request index, positions) so completion is
        # tracked per request as its last batch lands
        needed: "OrderedDict[int, List[int]]" = OrderedDict()
        remaining = []
        for ri, (_req, _q, bidx, _rows) in enumerate(routed):
            uniq = np.unique(bidx)
            remaining.append(len(uniq))
            for bi in uniq:
                needed.setdefault(int(bi), []).append(ri)
        # all batches any request needs, fetched in one coalesced stream —
        # with a mesh this is where cross-REQUEST work packs onto devices;
        # each batch's rows scatter as its logits land, so only O(world)
        # batch outputs are ever held beyond the LRU
        for bi, lg in self._iter_logits(list(needed)):
            touching = needed[bi]
            for ri in touching:
                req, q, bidx, rows = routed[ri]
                if req.logits is None:
                    req.logits = np.empty((len(q), lg.shape[1]), lg.dtype)
                sel = bidx == bi
                req.logits[sel] = lg[rows[sel]]
                remaining[ri] -= 1
                if remaining[ri] == 0:
                    req.done = True
                    req.latency_s = self.clock.now() - t0
        for req, q, _bidx, _rows in routed:          # empty requests
            if len(q) == 0:
                req.logits = np.zeros((0, self.cfg.out_dim), np.float32)
                req.done, req.latency_s = True, self.clock.now() - t0
        return {"requests": len(requests), "batch_runs_total":
                self.stats["batch_runs"], "time_s": self.clock.now() - t0}
