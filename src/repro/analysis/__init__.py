"""AST-based invariant analyzer for this repo (DESIGN.md §15).

``repro.analysis`` mechanically enforces the contracts the rest of the
codebase states in prose: deterministic plan builds (§8/§14), lock
discipline in the threaded serving tier (§11–§12), atomic artifact
writes (§12–§13), a single canonical fault-point registry (§12) and
jit-executable cache hygiene (§14).

Run it with ``python -m repro.analysis [--format json] [paths]``; see
``repro.analysis.cli``. The package is stdlib-only (``ast`` + ``re`` +
``json``) so the CI gate needs no scientific stack installed.
"""
from repro.analysis.model import (Checker, Finding, Module, Project,
                                  load_baseline)
from repro.analysis.determinism import DeterminismChecker
from repro.analysis.locks import LockDisciplineChecker
from repro.analysis.atomic_write import AtomicWriteChecker
from repro.analysis.fault_points import FaultPointChecker
from repro.analysis.jit_cache import JitCacheChecker

ALL_CHECKERS = (
    DeterminismChecker,
    LockDisciplineChecker,
    AtomicWriteChecker,
    FaultPointChecker,
    JitCacheChecker,
)

__all__ = [
    "ALL_CHECKERS",
    "AtomicWriteChecker",
    "Checker",
    "DeterminismChecker",
    "FaultPointChecker",
    "Finding",
    "JitCacheChecker",
    "LockDisciplineChecker",
    "Module",
    "Project",
    "load_baseline",
]
