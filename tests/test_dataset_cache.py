"""The synthetic dataset's on-disk cache is published whole: a reader in
another process (a test worker on a fresh checkout) finds the old state or
the complete file, never one that is still being written."""
import os

import numpy as np
import pytest

from repro.graph import datasets


def test_cache_round_trip_and_no_debris(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_DATA_DIR", str(tmp_path))
    monkeypatch.setattr(datasets, "_MEMO", {})
    built = datasets.get_dataset("tiny")
    path = datasets.cache_path(datasets.DATASET_SPECS["tiny"])
    assert os.listdir(tmp_path) == [os.path.basename(path)]
    monkeypatch.setattr(datasets, "_MEMO", {})
    loaded = datasets.get_dataset("tiny")
    assert loaded is not built
    np.testing.assert_array_equal(loaded.features, built.features)
    np.testing.assert_array_equal(loaded.graph.indices, built.graph.indices)


def test_failed_write_leaves_no_cache_file(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_DATA_DIR", str(tmp_path))
    monkeypatch.setattr(datasets, "_MEMO", {})

    def torn(f, **arrays):
        f.write(b"PK\x03\x04 half a zip")
        raise OSError("disk full")

    monkeypatch.setattr(datasets.np, "savez_compressed", torn)
    with pytest.raises(OSError):
        datasets.get_dataset("tiny")
    assert os.listdir(tmp_path) == []
