"""A whole run of the one-shot and GraphSAGE cells, off the chip and at a
tiny size, as ``test_faults.py`` runs the first two cells: ``correct``
holds for the program alone, and the one-shot cell's calls each miss the
plan cache.

    python -m pytest benchmarks/chip/tests
"""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness_cases  # noqa: E402

SEED = 2**33 + 29  # more than 32 bits: a seed need not fit a signed 32-bit int
TINY = {"ogbn-proteins-sage": {"nodes": 1024, "edges": 20480}}
CASES = [
    ("economics.oneshot", "program", True),
    ("economics.oneshot", "altered", False),
    ("economics.oneshot", "control", False),
    ("ogbn-proteins-sage.fullbatch", "program", True),
    ("ogbn-proteins-sage.fullbatch", "unchanged", False),
    ("ogbn-proteins-sage.fullbatch", "half_batch", False),
    ("ogbn-proteins-sage.fullbatch", "control", False),
]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = harness_cases.tiny_checkout(tmp_path_factory.mktemp("checkout"))
    for name, sizes in TINY.items():
        path = root / "benchmarks" / "chip" / "configs" / f"{name}.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()), **sizes)))
    return root


@pytest.fixture
def run(checkout, monkeypatch):
    """The checkout's harness with its look for a chip left out."""
    module = harness_cases.load_run(checkout)
    monkeypatch.setattr(module, "device_info", lambda chips: dict(harness_cases.FAKE_DEVICE))
    return module


@pytest.mark.parametrize("cell,case,correct", CASES)
def test_correct_only_for_the_program(run, checkout, cell, case, correct):
    line = harness_cases.run_cell(run, checkout, cell, case, SEED, 2.0)
    assert line["correct"] is correct, line["checks"]
    assert {"setup_s", "product_s" if "economics" in cell else "train_step_s"} == set(
        line["metrics"]
    )


def test_oneshot_misses_the_plan_cache_every_call(run, checkout):
    from repro.core import executor

    before = executor.cache_stats()["plan_misses"]
    line = harness_cases.run_cell(run, checkout, "economics.oneshot", "program", SEED, 2.0)
    calls = line["attempted"] + 1  # the window's calls and the warm call
    assert executor.cache_stats()["plan_misses"] - before == calls
