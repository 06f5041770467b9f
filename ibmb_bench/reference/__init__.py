"""Plain references of the benchmark's models, one module per model kind.

The reference of a configuration is the module named by its
``model["kind"]``: ``ibmb_bench/reference/<kind>.py``, found by
``for_model``. A new kind joins the benchmark as a new module here; no
other file names a kind. A reference module defines

* ``init_params(model, key, random_affine=False)``: weights drawn from
  ``key`` as the program draws them (``random_affine`` draws biases and
  normalisation parameters too, for served weights);
* ``forward(model, params, batch, dtype, key)``: the logits of every row
  of a reference batch (``graph.build_batch``), with dropout where ``key``
  is given;
* ``loss(model, params, batch, dtype, key)``: the mean cross-entropy of
  the batch's outputs;
* ``forward_flops(model, n, e)``: the model work of one forward over ``n``
  real nodes and ``e`` real edges, as ``flops.py`` describes;
* ``SMALL``: a small model dict of the kind, for the CPU tests.

It imports nothing of the program and takes nothing the program made.
``common`` and ``graph`` are helpers shared by the kinds, not kinds; a new
helper is a module that does not meet the contract.
"""
from __future__ import annotations

import importlib
import pkgutil
from types import ModuleType
from typing import List

CONTRACT = ("init_params", "forward", "loss", "forward_flops", "SMALL")


def for_model(model: dict) -> ModuleType:
    """The reference module of ``model["kind"]``. Raises where there is
    none, naming the file that would hold it, and where the module lacks a
    name of the contract."""
    kind = model["kind"]
    name, path = f"{__name__}.{kind}", f"ibmb_bench/reference/{kind}.py"
    try:
        mod = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ModuleNotFoundError(f"no reference for model kind {kind!r}: "
                                  f"{path} does not exist",
                                  name=name) from None
    missing = [a for a in CONTRACT if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"{path} lacks {missing} of the reference "
                             f"contract")
    return mod


def kinds() -> List[str]:
    """The kinds whose modules in this directory meet the contract; the
    helpers do not."""
    out = []
    for info in pkgutil.iter_modules(__path__):
        mod = importlib.import_module(f"{__name__}.{info.name}")
        if all(hasattr(mod, a) for a in CONTRACT):
            out.append(info.name)
    return sorted(out)
