"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see 1 CPU device
(contract §MULTI-POD 0); multi-device tests run in subprocesses."""

import importlib.util
import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

# make ``repro`` importable for a plain ``pytest`` invocation when the
# package is not pip-installed (no PYTHONPATH=src needed)
if importlib.util.find_spec("repro") is None:
    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


ROOT = Path(__file__).resolve().parents[1]


def q19_filters() -> list:
    """TPC-H Q19's chain as the benchmark's ``tpch-q19.orderkey-order``
    cell runs it: its driver's builder over its configuration file."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.drivers.scan_tree import tree_chain

    config = ROOT / "bench" / "configs" / "tpch-q19-lineitem-part.json"
    return tree_chain(json.loads(config.read_text()))
