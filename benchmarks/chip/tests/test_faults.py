"""A whole run of each cell, off the chip and at a tiny size, with the timed
path sound, broken underneath, or replaced by the lower-precision control:
``correct`` holds for the program alone.

    python -m pytest benchmarks/chip/tests
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness_cases  # noqa: E402

SEED = 2**33 + 17  # more than 32 bits, as the driver's seeds are
CASES = [
    ("economics.reuse", "program", True),
    ("economics.reuse", "altered", False),
    ("economics.reuse", "control", False),
    ("ogbn-arxiv-gcn.fullbatch", "program", True),
    ("ogbn-arxiv-gcn.fullbatch", "unchanged", False),
    ("ogbn-arxiv-gcn.fullbatch", "half_batch", False),
    ("ogbn-arxiv-gcn.fullbatch", "control", False),
]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return harness_cases.tiny_checkout(tmp_path_factory.mktemp("checkout"))


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
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)

