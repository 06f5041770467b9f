"""The harness finds every piece of a cell by name, counts model work as
the hand count does, refuses unknown devices and CPU-only hosts, and takes
a new configuration, cell and per-layer metric as new files alone."""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_helpers
from bench_helpers import REPO
from ibmb_bench import flops, harness, traffic

BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
BDIR = os.path.join(REPO, "ibmb_bench")


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    bench, entry, config, workload = harness.resolve_cell(REPO, cell)
    assert config["name"] == entry["config"]
    assert os.path.exists(os.path.join(BDIR, "modes",
                                       workload["mode"] + ".py"))
    for m in harness.cell_metrics(bench, cell, "per_layer"):
        mod = harness.load_module(os.path.join(BDIR, "metrics",
                                               m["name"] + ".py"))
        assert callable(mod.read)
    e2e = [m["name"] for m in harness.cell_metrics(bench, cell,
                                                   "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    model = config["model"]
    assert (model["in_dim"], model["out_dim"]) == (config["feat_dim"],
                                                  config["num_classes"])
    assert set(workload["limits"])


def test_configs_state_their_cuts():
    for entry in BENCH["configs"]:
        cfg = harness.load_json(os.path.join(REPO, entry["file"]))
        assert entry["reduced"] == cfg["reduced"]
        for key in cfg["reduced"]:
            assert cfg[key] < cfg["published"][key]


def test_mfu_counts_match_a_hand_count():
    gcn = dict(kind="gcn", in_dim=3, hidden=4, out_dim=2, num_layers=2,
               heads=1)
    # n=5 nodes, e=7 edges: 2·5·3·4 + 2·7·4 + 2·5·4·2 + 2·7·2
    assert flops.forward_flops(gcn, 5, 7) == 120 + 56 + 80 + 28
    gat = dict(kind="gat", in_dim=3, hidden=4, out_dim=2, num_layers=2,
               heads=2)
    # hidden: w = 4; output: w = 2 heads x 2 classes = 4
    hand = (2 * 5 * 3 * 4 + 4 * 5 * 4 + 2 * 7 * 4) + \
           (2 * 5 * 4 * 4 + 4 * 5 * 4 + 2 * 7 * 4)
    assert flops.forward_flops(gat, 5, 7) == hand
    assert flops.train_step_flops(gcn, 5, 7) == 3 * 284
    fields = {"node_mask": np.array([[1, 1, 0], [1, 0, 0]], np.float32),
              "edge_weight": np.array([[0.5, 0, 0.2], [0, 0, 0]])}
    nodes, edges = flops.real_counts(fields)
    assert nodes.tolist() == [2, 1] and edges.tolist() == [2, 0]


def test_unknown_device_kind_raises():
    peaks = harness.load_json(os.path.join(BDIR, "peaks.json"))
    assert harness.peak_for("TPU v5 lite", peaks)["bf16_flops_per_s"] > 0
    with pytest.raises(KeyError):
        harness.peak_for("TPU v99", peaks)


def test_run_without_a_tpu_exits_nonzero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "ibmb_bench", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "tpu" in p.stderr


def test_traffic_keeps_the_work_and_moves_the_order():
    params = dict(rate_per_s=50.0, ids_per_request={"min": 1, "max": 64},
                  zipf_exponent=1.1, base_seed=7)
    pool = np.arange(1000, 3000)
    a = traffic.schedule(params, 4.0, 2**31 + 5, pool)
    b = traffic.schedule(params, 4.0, 12, pool)
    assert len(a) == len(b) > 100
    assert sorted(len(i) for _, i in a) == sorted(len(i) for _, i in b)
    assert [len(i) for _, i in a] != [len(i) for _, i in b]
    assert a[-1][0] < 4.0 and b[-1][0] < 4.0
    for _, ids in a:
        assert len(set(ids.tolist())) == len(ids)
        assert np.isin(ids, pool).all()
    assert all(np.array_equal(x[1], y[1]) for x, y in
               zip(a, traffic.schedule(params, 4.0, 2**31 + 5, pool)))


def test_traffic_bursts_alternate_rates():
    params = dict(rate_per_s=100.0, ids_per_request={"min": 1, "max": 4},
                  zipf_exponent=1.1, base_seed=3,
                  burst={"on_s": 1.0, "off_s": 1.0, "off_rate_per_s": 5.0})
    due = np.array([t for t, _ in traffic.schedule(params, 8.0, 9,
                                                   np.arange(50))])
    on = ((due % 2.0) < 1.0).sum()
    assert on > 5 * (len(due) - on) > 0


def _digests(path):
    out = {}
    for dirpath, _dirs, files in os.walk(path):
        if ".cache" in dirpath or "__pycache__" in dirpath:
            continue
        for f in files:
            with open(os.path.join(dirpath, f), "rb") as fh:
                out[os.path.join(dirpath, f)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_cell_and_metric_are_files_alone(tmp_path):
    root = bench_helpers.make_root(str(tmp_path))
    bdir = os.path.join(root, "ibmb_bench")
    before = _digests(bdir)
    cfg = harness.load_json(os.path.join(root, BENCH["configs"][0]["file"]))
    cfg.update(name="gcn-tiny", num_nodes=500)
    for rel, obj in {"configs/gcn-tiny.json": cfg,
                     "workloads/gcn-tiny.score.json": {
                         "config": "gcn-tiny", "mode": "infer",
                         "split": "test", "limits": {"logit_gap": 1e-3}},
                     }.items():
        with open(os.path.join(bdir, rel), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(bdir, "metrics", "passes.score.py"), "w") as f:
        f.write("def read(record):\n"
                "    return record['counters'].get('passes')\n")
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "gcn-tiny", "source": "test",
                             "file": "ibmb_bench/configs/gcn-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gcn-tiny.score", "config": "gcn-tiny",
                               "traffic": "all_test", "chips": 1,
                               "why": "test"})
    [rate] = [m for m in bench["end_to_end"]
              if m["name"] == "infer_nodes_per_s"]
    rate["workloads"].append("gcn-tiny.score")
    bench["per_layer"].append({"name": "passes.score", "unit": "passes",
                               "better": "higher", "source": "program_counter",
                               "layer": "test", "moves": "infer_nodes_per_s",
                               "workloads": ["gcn-tiny.score"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _digests(bdir)
    # no file that was there changed: the new cell is new files alone
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 3

    res = bench_helpers.run(root, "gcn-tiny.score", seconds=0.5)
    assert res["correct"]
    assert set(res["metrics"]) == {"infer_nodes_per_s", "setup_s"}
    traced = bench_helpers.run(root, "gcn-tiny.score", seconds=0.5, trace=1)
    assert traced["metrics"]["passes.score"]["value"] >= 1
    assert list(traced)[-1] == "checks"
