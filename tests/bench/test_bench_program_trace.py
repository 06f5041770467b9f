"""The program's spans and scopes in a trace (``program_trace``) and the
per-layer metrics that read them, on hand-made planes and on a small
trace written from a text proto: spans clipped to the window, scopes
matched as a path component (inside ``transpose(jvp(...))`` too), each
reader's value, None where the program wrote nothing to read, and an
error where the trace cannot be read or is another run's."""
import os
import shutil

import pytest

from bench_helpers import REPO  # also puts the repo on sys.path
from ibmb_bench import harness, program_trace as pt, trace_reduce as tr
from ibmb_bench import trace_report
from ibmb_bench.trace_reduce import Event, Plane

BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))


def _trace(host, ops, paths=None):
    """A Trace of one host line (``(name, start, dur[, args])``) and one
    device's ops (``(name, start, dur)``), ``paths`` naming each op's
    scope path."""
    events = [(Event(*h[:3]), h[3] if len(h) > 3 else {}) for h in host]
    planes = [Plane("/host:CPU", {"python": [e for e, _ in events]}),
              Plane("/device:TPU:0", {"XLA Ops": [Event(*o) for o in ops]})]
    spans = [(e, a) for e, a in events if e.name.startswith("ibmb/")]
    return pt.Trace(planes, spans, dict(paths or {}))


def test_scope_is_the_innermost_known_component():
    assert pt.scope_of("jit(train_step)/jit(main)/layer0/aggregate/"
                       "scatter-add") == "aggregate"
    assert pt.scope_of("jit(train_step)/transpose(jvp(layer1))/attention/"
                       "exp") == "attention"
    assert pt.scope_of("transpose(jvp(layer0/aggregate))/gather") == \
        "aggregate"
    assert pt.scope_of("jit(f)/optimizer/loss_scale/mul") == "optimizer"
    assert pt.scope_of("jit(f)/layer0/norm/add:") == "norm"
    assert pt.scope_of("jit(f)/aggregated/x") == pt.NO_SCOPE
    assert pt.scope_of("") == pt.NO_SCOPE


def test_spans_are_counted_and_clipped_to_the_window():
    t = _trace([("bench/window", 10, 100), ("ibmb/fit/step", 0, 20),
                ("ibmb/fit/step", 30, 20), ("ibmb/fit/step", 105, 20),
                ("ibmb/fit/step", 120, 5),
                ("ibmb/loader/stage", 40, 10, {"bytes": 300.0}),
                ("ibmb/loader/stage", 60, 10, {"bytes": 200.0}),
                ("bench/fit", 10, 100)], [("a", 0, 1)])
    got = pt.span_totals(t, 10, 110)
    assert got["ibmb/fit/step"]["count"] == 3        # the last one is out
    assert got["ibmb/fit/step"]["total_s"] == pytest.approx(35e-9)
    assert got["ibmb/loader/stage"]["args"] == {"bytes": 500.0}
    assert "bench/fit" not in got


def test_scopes_split_device_time_and_average_over_devices():
    t = _trace([("bench/window", 0, 100)],
               [("fusion.1", 0, 30), ("fusion.2", 40, 10),
                ("fusion.3", 60, 10), ("copy.4", 80, 5), ("fusion.1", 95, 10)],
               {"fusion.1": "jit(s)/transpose(jvp(layer0))/aggregate/"
                            "scatter-add",
                "fusion.2": "jit(s)/layer1/attention/exp",
                "fusion.3": "jit(s)/layer0/transform/dot_general"})
    got = pt.scope_seconds(t, 1, 0, 100)
    assert got == pytest.approx({"aggregate": 35e-9, "attention": 10e-9,
                                 "transform": 10e-9, pt.NO_SCOPE: 5e-9})
    t.planes.append(Plane("/device:TPU:1", {"XLA Ops": [
        Event("fusion.2", 0, 50)]}))
    two = pt.scope_seconds(t, 2, 0, 100)
    assert two["attention"] == pytest.approx((10e-9 + 50e-9) / 2)
    assert two["aggregate"] == pytest.approx(35e-9 / 2)


def test_idle_inside_program_spans():
    t = _trace([("bench/window", 0, 100), ("bench/fit", 0, 100),
                ("ibmb/fit/epoch", 0, 60), ("ibmb/loader/wait", 70, 10)],
               [("a", 10, 10), ("b", 40, 10), ("c", 85, 5)])
    # gaps [0,10] [20,40] [50,85] [90,100]: the first two lie in the
    # epoch, the third's midpoint (67.5) in none, the last in none
    assert pt.idle_in_spans(t, 0, 100) == pytest.approx(30e-9)


SYNTH = """
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 50000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 1048576 } }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 3145728 } }
    events { metadata_id: 5 offset_ps: 20000000 duration_ps: 6000000 }
    events { metadata_id: 6 offset_ps: 30000000 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "ibmb/fit/schedule" } }
  event_metadata { key: 3 value { id: 3 name: "ibmb/loader/wait" } }
  event_metadata { key: 4 value { id: 4 name: "ibmb/loader/stage" } }
  event_metadata { key: 5 value { id: 5 name: "ibmb/fit/loss_sync" } }
  event_metadata { key: 6 value { id: 6 name: "ibmb/engine/forward" } }
  stat_metadata { key: 1 value { id: 1 name: "bytes" } }
}
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 10000000 duration_ps: 12000000 }
    events { metadata_id: 8 offset_ps: 40000000 duration_ps: 6000000 }
    events { metadata_id: 9 offset_ps: 60000000 duration_ps: 2000000 } }
  event_metadata { key: 7 value { id: 7 name: "fusion.24"
    stats { metadata_id: 1
            str_value: "jit(f)/transpose(jvp(layer0))/aggregate/scatter-add" } } }
  event_metadata { key: 8 value { id: 8 name: "fusion.3"
    stats { metadata_id: 1 ref_value: 3 } } }
  event_metadata { key: 9 value { id: 9 name: "copy.1" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "jit(f)/layer1/attention/exp" } }
}
"""

# window 100 us; 2 epochs, 4 steps, 2 batches
COUNTERS = dict(epochs=2, steps=4, batches=2)
WINDOW_S = 100e-6
EXPECTED = {
    "schedule_ms_per_epoch.train": 6e-3 / 2,
    "stage_wait_ms_per_step.train": 4e-3 / 4,
    "stage_mb_per_step.train": 4.0 / 4,
    "host_sync_ms_per_step.train": 6e-3 / 4,
    "aggregate_ms_per_step.train": 12e-3 / 4,
    "dispatch_ms_per_batch.infer": 8e-3 / 2,
    "aggregate_ms_per_batch.infer": 12e-3 / 2,
    "attention_ms_per_batch.infer": 6e-3 / 2,
}
BARE = SYNTH.replace('name: "ibmb/', 'name: "other/').replace(
    'name: "tf_op"', 'name: "hlo_op"')


def _checkout(tmp_path, text):
    """A checkout with the metrics and a trace written where the harness
    writes one."""
    from jax.profiler import ProfileData
    bdir = tmp_path / "ibmb_bench"
    shutil.copytree(os.path.join(REPO, "ibmb_bench", "metrics"),
                    bdir / "metrics")
    tdir = bdir / ".cache" / "traces" / "cell" / "plugins" / "profile" / "t"
    tdir.mkdir(parents=True)
    (tdir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return bdir


def _record(window_s=WINDOW_S):
    return dict(trace=dict(window_s=window_s, busy_s=0.02, devices=1),
                counters=dict(COUNTERS), flops=None, peak={})


def test_every_new_metric_is_registered_for_its_cell():
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in EXPECTED:
        m = per_layer[name]
        assert m["source"] == "device_trace" and len(m["workloads"]) == 1
        cell = m["workloads"][0]
        assert name.endswith(cell.split(".")[-1])
        assert m["moves"] in [e["name"] for e in harness.cell_metrics(
            BENCH, cell, "end_to_end")]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_its_span_or_scope(tmp_path, name):
    bdir = _checkout(tmp_path, SYNTH)
    reader = harness.load_module(str(bdir / "metrics" / (name + ".py")))
    assert reader.read(_record()) == pytest.approx(EXPECTED[name])
    # no trace, or a program without the spans
    assert reader.read(dict(_record(), trace=None)) is None
    bare = _checkout(tmp_path / "bare", BARE)
    reader = harness.load_module(str(bare / "metrics" / (name + ".py")))
    assert reader.read(_record()) is None


def test_a_trace_that_is_not_the_runs_raises(tmp_path):
    bdir = _checkout(tmp_path, SYNTH)
    x = str(bdir / "metrics" / "x.py")
    with pytest.raises(ValueError, match="window"):     # another run's
        pt.for_record(_record(window_s=2 * WINDOW_S), x)
    for p in (bdir / ".cache").rglob("*.xplane.pb"):
        p.write_bytes(b"not a trace")
    with pytest.raises(Exception):                      # unreadable
        pt.for_record(_record(), x)
    shutil.rmtree(bdir / ".cache")
    with pytest.raises(FileNotFoundError):              # none written
        pt.for_record(_record(), x)


def test_readers_of_one_run_read_the_trace_once(tmp_path):
    bdir = _checkout(tmp_path, SYNTH)
    record = _record()
    first = pt.for_record(record, str(bdir / "metrics" / "x.py"))
    assert first["spans"]["ibmb/fit/schedule"]["count"] == 2
    assert first["harness_spans"]["bench/window"]["total_s"] == \
        pytest.approx(WINDOW_S)
    assert first["unscoped_ops"] == [["copy.1", pytest.approx(2e-6)]]
    assert pt.for_record(record, str(bdir / "metrics" / "x.py")) is first


TPU_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tpu_scope_trace.xplane.pb")


def test_recorded_tpu_trace(tmp_path):
    """A trace taken on one TPU v5e of a jitted training step whose ops sit
    in ``layer0/{transform,aggregate,norm}`` under a ``loss`` scope, with
    an ``ibmb/probe`` span (args ``batch=1``, ``bytes=99``) around it: the
    scope paths are read from the ops' metadata, where the chip keeps them
    (the events themselves carry the op's HLO text)."""
    trace = pt.load(TPU_TRACE)
    assert any(p.startswith("jit(step)/transpose(jvp(loss))/layer0/"
                            "aggregate/") for p in trace.op_paths.values())
    lo, hi = tr.window_of(trace.planes)
    scopes = pt.scope_seconds(trace, 1, lo, hi)
    busy = tr.reduce_planes(trace.planes, 1)["busy_s"]
    assert {"aggregate", "transform", "norm", "loss"} <= set(scopes)
    assert scopes["aggregate"] > 0.9 * busy
    assert sum(scopes.values()) == pytest.approx(busy, rel=0.05)
    spans = pt.span_totals(trace, lo, hi)
    assert spans["ibmb/probe"]["count"] == 1
    assert spans["ibmb/probe"]["args"] == {"batch": 1.0, "bytes": 99.0}

    shutil.copy(TPU_TRACE, tmp_path / "t.xplane.pb")
    got = trace_report.report(str(tmp_path))
    assert got["scopes"] == pytest.approx(scopes)
    assert got["idle_in_ibmb_spans_share"] > 0.99     # all in the probe
    assert got["spans"] == spans
    assert got["unscoped_ops"] and all(
        name.startswith("%copy") for name, _ in got["unscoped_ops"])
    assert sum(s for _, s in got["unscoped_ops"]) == pytest.approx(
        scopes.get(pt.NO_SCOPE, 0.0))
    assert got["harness_spans"] == {}      # taken without the harness


def test_a_reader_can_ask_for_a_scope_not_in_scopes(tmp_path):
    """A new reader file asks ``for_record`` for a name beyond ``SCOPES``
    and reads it; the readers that ask for nothing read as before."""
    bdir = _checkout(tmp_path, SYNTH)
    (bdir / "metrics" / "exp_ms_per_batch.new.py").write_text(
        "from ibmb_bench import program_trace\n\n\n"
        "def read(record):\n"
        "    got = program_trace.for_record(record, __file__,\n"
        "                                   scopes=('exp',))\n"
        "    seconds = got['scopes'].get('exp') if got else None\n"
        "    return None if seconds is None else \\\n"
        "        1e3 * seconds / record['counters']['batches']\n")
    old = {name: harness.load_module(str(bdir / "metrics" / (name + ".py")))
           for name in EXPECTED}
    alone = {name: r.read(_record()) for name, r in old.items()}
    record = _record()
    new = harness.load_module(str(bdir / "metrics" /
                                  "exp_ms_per_batch.new.py"))
    assert new.read(record) == pytest.approx(6e-3 / 2)
    assert {name: r.read(record) for name, r in old.items()} == alone
    assert pt.for_record(record, str(bdir / "metrics" / "x.py"))[
        "scopes"] == pytest.approx({"aggregate": 12e-6, "attention": 6e-6,
                                    pt.NO_SCOPE: 2e-6})
    assert new.read(dict(_record(), trace=None)) is None
    bare = _checkout(tmp_path / "bare", BARE)
    assert pt.for_record(_record(), str(bare / "metrics" / "x.py"),
                         scopes=("exp",)) is None


def test_recorded_tpu_trace_reads_a_new_scope_name(tmp_path):
    """On the trace from one v5e: ``scatter-add``, a name inside the
    ``aggregate`` scope, takes the aggregation's scatters from it, and the
    scopes still add up to the same device time."""
    bdir = tmp_path / "ibmb_bench"
    tdir = bdir / ".cache" / "traces" / "cell"
    tdir.mkdir(parents=True)
    shutil.copy(TPU_TRACE, tdir / "t.xplane.pb")
    trace = pt.load(TPU_TRACE)
    lo, hi = tr.window_of(trace.planes)
    scopes = pt.scope_seconds(trace, 1, lo, hi)
    record = dict(trace=dict(window_s=(hi - lo) / 1e9, devices=1))
    x = str(bdir / "metrics" / "x.py")
    got = pt.for_record(record, x, scopes=("scatter-add",))["scopes"]
    assert got["scatter-add"] > 0.95 * scopes["aggregate"]
    assert got["aggregate"] == pytest.approx(
        scopes["aggregate"] - got["scatter-add"])
    assert sum(got.values()) == pytest.approx(sum(scopes.values()))
    assert pt.for_record(record, x)["scopes"] == scopes
