"""The invariant analyzer (DESIGN.md §15): every rule must both fire on
its known-bad fixture and stay silent on the known-good one; suppression
(allow comments + baseline) has exact semantics; the repo itself is
clean end to end; and the real violations the analyzer surfaced are
pinned by behavioral regression tests so they cannot quietly return."""
import inspect
import json
import time
from pathlib import Path

import numpy as np
import pytest

from conftest import FakeClock
from repro.analysis.atomic_write import AtomicWriteChecker
from repro.analysis.cli import find_repo_root, main
from repro.analysis.determinism import DeterminismChecker
from repro.analysis.fault_points import FaultPointChecker
from repro.analysis.jit_cache import JitCacheChecker
from repro.analysis.locks import LockDisciplineChecker
from repro.analysis.model import (BASELINE_RELPATH, Finding, Module, Project,
                                  filter_allowed, filter_baselined,
                                  load_baseline)

FIXTURES = Path(__file__).parent / "fixtures" / "analysis"
REPO_ROOT = Path(find_repo_root())


def _snippet_project(relpath, fixture):
    """Map a fixture snippet onto a virtual path inside a checker's scope."""
    return Project.from_sources(
        {relpath: (FIXTURES / fixture).read_text()})


def _run(checker_cls, project):
    return checker_cls().run(project)


# ------------------------------------------------------------- determinism
def test_determinism_fires_on_known_bad():
    proj = _snippet_project("src/repro/core/x.py", "determinism_bad.py")
    found = _run(DeterminismChecker, proj)
    assert len(found) == 6
    assert all(f.rule == "determinism" for f in found)
    msgs = "\n".join(f.message for f in found)
    for needle in ("wall-clock", "unseeded", "global-state RNG",
                   "`id()` is salted"):
        assert needle in msgs
    assert sum("iterating a set" in f.message for f in found) == 2


def test_determinism_silent_on_known_good():
    proj = _snippet_project("src/repro/core/x.py", "determinism_good.py")
    kept, suppressed = filter_allowed(_run(DeterminismChecker, proj), proj)
    assert kept == []
    assert len(suppressed) == 2  # the two annotated timing-only reads


def test_determinism_scope_excludes_serving_tier():
    proj = _snippet_project("src/repro/serve/x.py", "determinism_bad.py")
    assert _run(DeterminismChecker, proj) == []


# ---------------------------------------------------------- lock discipline
def test_locks_fire_on_known_bad():
    proj = _snippet_project("src/repro/serve/x.py", "locks_bad.py")
    by_rule = {}
    for f in _run(LockDisciplineChecker, proj):
        by_rule.setdefault(f.rule, []).append(f)
    # the A->B and B->A edges each close the cycle
    assert len(by_rule["lock-order"]) == 2
    # open + json.dump under lock, future.result, time.sleep
    assert len(by_rule["lock-blocking"]) == 4
    assert len(by_rule["condvar-wait"]) == 1
    assert len(by_rule["clock-injectable"]) == 1


def test_locks_good_needs_only_the_justified_allow():
    proj = _snippet_project("src/repro/serve/x.py", "locks_good.py")
    kept, suppressed = filter_allowed(
        _run(LockDisciplineChecker, proj), proj)
    assert kept == []
    # engine.run under the per-tenant lock is by design and annotated;
    # SystemClock's own time.* lines are exempt by name, the predicate-
    # looped condvar wait and the consistent A->B order are simply clean
    assert [f.rule for f in suppressed] == ["lock-blocking"]


# ------------------------------------------------------------- atomic write
def test_atomic_write_fires_on_known_bad():
    proj = _snippet_project("src/repro/ooc/x.py", "atomic_bad.py")
    found = _run(AtomicWriteChecker, proj)
    assert len(found) == 2
    assert all("os.replace" in f.message for f in found)


def test_atomic_write_silent_on_known_good():
    proj = _snippet_project("src/repro/ooc/x.py", "atomic_good.py")
    assert _run(AtomicWriteChecker, proj) == []


def test_atomic_write_scope_excludes_serving_tier():
    proj = _snippet_project("src/repro/serve/x.py", "atomic_bad.py")
    assert _run(AtomicWriteChecker, proj) == []


# ----------------------------------------------------------------- jit-cache
def test_jit_cache_fires_on_known_bad():
    proj = _snippet_project("src/repro/serve/x.py", "jit_bad.py")
    found = _run(JitCacheChecker, proj)
    assert len(found) == 3
    msgs = "\n".join(f.message for f in found)
    assert "inside a loop" in msgs
    assert "per-request entry" in msgs


def test_jit_cache_silent_on_known_good():
    proj = _snippet_project("src/repro/serve/x.py", "jit_good.py")
    assert _run(JitCacheChecker, proj) == []


# ------------------------------------------------------ fault-point registry
def test_fault_registry_drift_fires():
    proj = Project.load(str(FIXTURES / "faultreg_bad"))
    found = _run(FaultPointChecker, proj)
    assert len(found) == 5
    joined = "\n".join(f.message for f in found)
    assert "unregistered fault point `unknown`" in joined
    assert "non-literal point name" in joined
    assert "`stale` has no injection site" in joined
    assert "`stale` missing from the" in joined
    assert "`ghost` which is" in joined


def test_fault_registry_in_sync_is_silent():
    proj = Project.load(str(FIXTURES / "faultreg_good"))
    assert _run(FaultPointChecker, proj) == []


# ------------------------------------------------------ suppression semantics
_ALLOW_SRC = """\
import time

def a():
    t = time.time()  # lint: allow(determinism) — same line
    # lint: allow(determinism) — comment-only line above
    u = time.time()
    v = 0  # lint: allow(determinism) on a CODE line, not a comment
    w = time.time()
    x = time.time()
    return t, u, v, w, x
"""


def test_allow_comment_semantics():
    mod = Module("src/repro/core/x.py", _ALLOW_SRC)
    assert mod.allowed("determinism", 4)        # trailing, same line
    assert mod.allowed("determinism", 6)        # comment-only line above
    assert not mod.allowed("determinism", 8)    # previous line is code
    assert not mod.allowed("determinism", 9)    # no annotation at all
    assert not mod.allowed("lock-order", 4)     # rule name must match


def test_allow_comments_filter_end_to_end():
    proj = Project.from_sources({"src/repro/core/x.py": _ALLOW_SRC})
    kept, suppressed = filter_allowed(_run(DeterminismChecker, proj), proj)
    assert sorted(f.line for f in kept) == [8, 9]
    assert sorted(f.line for f in suppressed) == [4, 6]


def test_baseline_matching_semantics():
    f1 = Finding("determinism", "src/a.py", 10, "m")
    f2 = Finding("determinism", "src/b.py", 10, "m")
    f3 = Finding("lock-order", "src/a.py", 10, "m")
    baseline = [
        {"rule": "determinism", "path": "src/a.py"},            # any line
        {"rule": "lock-order", "path": "src/a.py", "line": 11},  # wrong line
    ]
    kept, matched = filter_baselined([f1, f2, f3], baseline)
    assert matched == [f1]
    assert kept == [f2, f3]


def test_shipped_baseline_is_empty():
    # acceptance: real violations were fixed, not baselined
    assert load_baseline(str(REPO_ROOT / BASELINE_RELPATH)) == []
    assert load_baseline(str(REPO_ROOT / "no-such-baseline.json")) == []


# ------------------------------------------------------------- CLI + smoke
def test_repo_is_clean_end_to_end(capsys):
    rc = main(["--format", "json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["findings"] == []
    assert report["suppressed"]["baseline"] == []
    lock_rules = {"lock-order", "lock-blocking", "condvar-wait",
                  "clock-injectable"}
    allowed = report["suppressed"]["allow_comments"]
    # by-design lock suppressions stay within the reviewed budget; every
    # other allow is an annotated timing-only determinism read
    assert len([f for f in allowed if f["rule"] in lock_rules]) <= 3
    assert {f["rule"] for f in allowed} <= lock_rules | {"determinism"}


def test_cli_nonzero_exit_and_json_report_on_drift(capsys):
    rc = main(["--root", str(FIXTURES / "faultreg_bad"), "--format", "json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert len(report["findings"]) == 5
    assert {f["rule"] for f in report["findings"]} == {"fault-point"}


def test_cli_only_and_path_filters(capsys):
    # --only selects checkers; a path argument narrows reported findings
    rc = main(["--root", str(FIXTURES / "faultreg_bad"),
               "--only", "jit-cache"])
    capsys.readouterr()
    assert rc == 0
    rc = main(["--root", str(FIXTURES / "faultreg_bad"), "tools"])
    capsys.readouterr()
    assert rc == 0  # all drift findings live under src/ and DESIGN.md


# ---------------------------------------------------------------- regressions
# Behavioral pins for the real violations the analyzer surfaced (ISSUE:
# fixed, not baselined).

def test_heartbeats_default_clock_survives_wallclock_jump(monkeypatch):
    from repro.train.elastic import Heartbeats

    hb = Heartbeats(timeout_s=60.0)
    hb.beat(0)
    # an NTP step / DST jump moves time.time by hours; the monotonic
    # SystemClock default must not declare every host dead (the old
    # `self._now = time.time` default did exactly that)
    monkeypatch.setattr(time, "time", lambda: time.monotonic() + 7200.0)
    assert hb.dead_hosts() == []


def test_heartbeats_timeout_under_fake_clock():
    from repro.train.elastic import Heartbeats

    clock = FakeClock()
    hb = Heartbeats(timeout_s=10.0, clock=clock)
    hb.beat(0)
    hb.beat(1)
    clock.advance(5.0)
    hb.beat(1)
    clock.advance(6.0)
    assert hb.dead_hosts() == [0]


def test_atomic_write_json_failed_serialize_keeps_original(tmp_path):
    from repro.ioutil import atomic_write_json

    path = tmp_path / "bench.json"
    atomic_write_json(str(path), {"ok": 1})
    with pytest.raises(TypeError):
        atomic_write_json(str(path), {"bad": object()})
    assert json.loads(path.read_text()) == {"ok": 1}
    assert list(tmp_path.iterdir()) == [path]  # no .tmp debris


def test_atomic_write_text_failed_publish_keeps_original(tmp_path,
                                                         monkeypatch):
    from repro import ioutil

    path = tmp_path / "artifact.txt"
    path.write_text("old")

    def boom(src, dst):
        raise OSError("device gone")

    monkeypatch.setattr(ioutil.os, "replace", boom)
    with pytest.raises(OSError):
        ioutil.atomic_write_text(str(path), "new")
    assert path.read_text() == "old"
    assert list(tmp_path.iterdir()) == [path]


def test_ppr_partition_is_a_pure_function_of_seed(tiny_ds):
    from repro.core.partition import ppr_distance_partition
    from repro.core.ppr import push_appr

    outputs = tiny_ds.splits["train"]
    ppr = push_appr(tiny_ds.graph, outputs, topk=32)
    a = ppr_distance_partition(ppr, outputs, 16, seed=7)
    b = ppr_distance_partition(ppr, outputs, 16, seed=7)
    assert len(a) == len(b)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_gnn_engine_latency_flows_through_injected_clock(tiny_ds):
    import jax

    from repro.core import IBMBConfig, IBMBPipeline
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.serve import GNNInferenceEngine, GNNRequest

    pipe = IBMBPipeline(tiny_ds, IBMBConfig(
        variant="node", k_per_output=8, max_outputs_per_batch=32,
        pad_multiple=16))
    plan = pipe.plan("test", for_inference=True)
    cfg = GNNConfig(kind="gcn", in_dim=tiny_ds.feat_dim, hidden=32,
                    out_dim=tiny_ds.num_classes, num_layers=2)
    params = init_gnn(cfg, jax.random.PRNGKey(0))

    clock = FakeClock(100.0)
    eng = GNNInferenceEngine(plan, cfg, params, clock=clock)
    req = GNNRequest(node_ids=plan.routing.node_ids[:4])
    stats = eng.run([req])
    # a frozen fake clock means the recorded latencies are exactly zero —
    # proof the engine never consults the wall clock directly
    assert req.done
    assert req.latency_s == 0.0
    assert stats["time_s"] == 0.0


def test_serve_engine_accepts_injected_clock():
    from repro.serve.engine import ServeEngine

    assert "clock" in inspect.signature(ServeEngine.__init__).parameters
