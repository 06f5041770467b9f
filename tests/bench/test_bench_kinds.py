"""A model kind joins the benchmark as new files only: a toy kind, planted
as ``ibmb_bench.reference.toy`` in ``sys.modules``, is found by name, counts
its own work, and passes its own model fields to the program; a kind with
no module names the file it lacks; and no harness file outside
``reference/`` names a kind."""
import dataclasses
import os
import re
import sys
import types

import pytest

from bench_helpers import REPO  # also puts the repo on sys.path
from ibmb_bench import data, flops, harness, reference

BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
TOY = dict(kind="toy", in_dim=3, hidden=4, out_dim=2, num_layers=2,
           temperature=0.5)


def _toy_module():
    mod = types.ModuleType("ibmb_bench.reference.toy")
    mod.SMALL = dict(TOY)
    mod.init_params = lambda model, key, random_affine=False: {}
    mod.forward = lambda model, params, batch, dtype=None, key=None: None
    mod.loss = lambda model, params, batch, dtype=None, key=None: 0.0
    mod.forward_flops = lambda model, n, e: 7 * n + 11 * e
    return mod


@pytest.fixture
def toy(monkeypatch):
    mod = _toy_module()
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_a_planted_kind_is_found_and_counts_its_own_work(toy):
    assert reference.for_model(TOY) is toy
    assert flops.forward_flops(TOY, 5, 3) == 7 * 5 + 11 * 3
    assert flops.train_step_flops(TOY, 5, 3) == 3 * (7 * 5 + 11 * 3)


def test_a_planted_kind_passes_its_own_fields(toy, monkeypatch):
    from repro.models.gnn import models
    toy_config = dataclasses.make_dataclass(
        "ToyConfig", [("temperature", float, 1.0)],
        bases=(models.GNNConfig,), frozen=True)
    monkeypatch.setattr(models, "GNNConfig", toy_config)
    cfg = data.model_config(types.SimpleNamespace(model=TOY))
    assert (cfg.kind, cfg.hidden, cfg.temperature) == ("toy", 4, 0.5)
    with pytest.raises(TypeError, match="block_type"):
        data.model_config(types.SimpleNamespace(
            model=dict(TOY, block_type="res+")))


def test_a_field_the_program_lacks_raises():
    with pytest.raises(TypeError, match="temperature"):
        data.model_config(types.SimpleNamespace(model=TOY))


def test_a_kind_without_a_module_names_the_file():
    with pytest.raises(ModuleNotFoundError,
                       match="ibmb_bench/reference/nosuch.py"):
        reference.for_model(dict(TOY, kind="nosuch"))
    with pytest.raises(AttributeError, match="init_params"):
        reference.for_model(dict(TOY, kind="common"))   # a helper


def test_kinds_are_the_modules_that_meet_the_contract():
    found = reference.kinds()
    assert {"gcn", "gat"} <= set(found)
    assert not {"common", "graph"} & set(found)
    for kind in found:
        mod = reference.for_model({"kind": kind})
        assert mod.SMALL["kind"] == kind


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_todays_configs_pass_exactly_their_fields(entry):
    fields = ("kind", "in_dim", "hidden", "out_dim", "num_layers", "heads",
              "dropout", "dtype")
    model = harness.load_json(os.path.join(REPO, entry["file"]))["model"]
    assert sorted(model) == sorted(fields)
    got = data.model_config(types.SimpleNamespace(model=model))
    assert [getattr(got, f) for f in fields] == [model[f] for f in fields]
    assert reference.for_model(model).SMALL["kind"] == model["kind"]


def test_no_harness_file_outside_reference_names_a_kind():
    bench = os.path.join(REPO, "ibmb_bench")
    literal = re.compile(r"[\"'](gcn|gat)[\"']")
    found = []
    for root, dirs, files in os.walk(bench):
        dirs[:] = [d for d in dirs if d not in ("reference", ".cache")]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    found += [f"{path}:{i}" for i, line in enumerate(fh, 1)
                              if literal.search(line)]
    assert found == []
