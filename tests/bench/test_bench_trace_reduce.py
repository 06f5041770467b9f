"""The trace reduction, on hand-made planes and on a small trace recorded on
the CPU (``cpu_trace.xplane.pb``: a ``bench/window`` span around four
``bench/step`` calls of a jitted matmul, each followed by a 5 ms
``bench/sleep``)."""
import os

import pytest

import bench_helpers  # noqa: F401  (puts the repo on sys.path)
from ibmb_bench import trace_reduce as tr
from ibmb_bench.trace_reduce import Event, Plane

HERE = os.path.dirname(os.path.abspath(__file__))
CPU_TRACE = os.path.join(HERE, "cpu_trace.xplane.pb")


def _host(*events):
    return Plane("/host:CPU", {"python": [Event(*e) for e in events]})


def _device(i, *ops):
    return Plane(f"/device:TPU:{i}", {"XLA Ops": [Event(*o) for o in ops]})


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 15), (0, 10), (20, 30), (30, 31)]) == [
        (0, 15), (20, 31)]


def test_busy_gaps_and_labels_exact():
    planes = [
        _host(("bench/window", 0, 40), ("outer", 0, 40),
              ("bench/sleep", 14, 8), ("bench/submit", 29, 12)),
        _device(0, ("fusion.1", 0, 10), ("fusion.2", 5, 10),
                ("scatter.3", 20, 10), ("fusion.1", 45, 5)),
    ]
    s = tr.reduce_planes(planes, 1)
    assert s["window_s"] == pytest.approx(40e-9)
    assert s["busy_s"] == pytest.approx(25e-9)       # [0,15] + [20,30]
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"bench/sleep": 5e-9, "bench/submit": 10e-9})
    ops = dict(s["top_ops"])
    assert ops["fusion.1"] == pytest.approx(10e-9)    # the op past the window
    assert ops["scatter.3"] == pytest.approx(10e-9)   # does not count


def test_busy_is_averaged_over_the_devices_used():
    planes = [_host(("bench/window", 0, 100)),
              _device(0, ("a", 0, 50)), _device(1, ("a", 0, 30)),
              _device(2, ("a", 0, 100))]
    assert tr.reduce_planes(planes, 2)["busy_s"] == pytest.approx(40e-9)
    assert tr.reduce_planes(planes, 3)["devices"] == 3


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes([_host(("bench/window", 0, 10))], 1)


def test_recorded_cpu_trace():
    planes = tr.load_planes(CPU_TRACE)
    host = [p for p in planes if p.name == "/host:CPU"][0]
    # on the CPU the ops run on the client's host threads: present them as
    # a device plane, the way a TPU trace has its ops
    ops = [e for name, evs in host.lines.items()
           if name.startswith("tf_XLAPjRtCpuClient") for e in evs
           if e.dur_ns > 0 and not e.name.startswith(("end:", "Threadpool"))]
    assert ops
    planes.append(Plane("/device:CPU:0", {"XLA Ops": ops}))
    s = tr.reduce_planes(planes, 1)
    window = [e for e in host.lines["python"] if e.name == "bench/window"][0]
    assert s["window_s"] == pytest.approx(window.dur_ns / 1e9)
    merged = tr.clip(tr.union([(e.start_ns, e.end_ns) for e in ops]),
                     window.start_ns, window.end_ns)
    assert s["busy_s"] == pytest.approx(sum(b - a for a, b in merged) / 1e9)
    assert 0 < s["busy_s"] < s["window_s"]
    idle = dict(s["idle_gaps"])
    # four 5 ms sleeps, and the device idle through each of them
    assert idle["bench/sleep"] >= 4 * 0.005 * 0.9
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert any(name.startswith("dot") for name, _ in s["top_ops"])


def test_find_xplane_finds_the_newest(tmp_path):
    d = tmp_path / "plugins" / "profile"
    for stamp in ("2026_01_01_00_00_00", "2026_01_01_00_00_09"):
        (d / stamp).mkdir(parents=True)
        (d / stamp / "host.xplane.pb").write_bytes(b"")
    assert tr.find_xplane(str(tmp_path)).endswith(
        os.path.join("2026_01_01_00_00_09", "host.xplane.pb"))
