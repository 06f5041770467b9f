"""Where the entry points keep JAX's compile cache and the dataset cache."""
import dataclasses
import os

import jax
import numpy as np

from repro import compile_cache
from repro.graph import datasets
from repro.graph.synthetic import DATASET_SPECS


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    want = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.enable_compile_cache() == want   # same each call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_changed_spec_misses_dataset_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_DATA_DIR", str(tmp_path))
    spec = DATASET_SPECS["tiny"]
    ds = datasets.get_dataset("tiny")
    assert os.path.exists(datasets.cache_path(spec))
    edited = dataclasses.replace(spec, seed=spec.seed + 1)
    assert datasets.cache_path(edited) != datasets.cache_path(spec)
    monkeypatch.setitem(DATASET_SPECS, "tiny", edited)
    fresh = datasets.get_dataset("tiny")
    assert not np.array_equal(fresh.features, ds.features)
    assert os.path.dirname(datasets.cache_path(spec)) == str(tmp_path)


def test_dataset_cache_lives_in_checkout(monkeypatch):
    monkeypatch.delenv("REPRO_DATA_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = datasets.cache_path(DATASET_SPECS["tiny"])
    assert os.path.dirname(path) == os.path.join(root, ".data_cache")
