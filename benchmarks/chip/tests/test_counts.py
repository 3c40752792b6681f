"""The yardstick's counts against hand counts on tiny problems.

    python -m pytest benchmarks/chip/tests
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counts  # noqa: E402

# A = [[1, 0, 1], [0, 1, 0], [1, 1, 1]] as CSR; C = A @ A has rows
# {0, 1, 2}, {1}, {0, 1, 2}.
INDPTR = np.array([0, 2, 3, 6])
INDICES = np.array([0, 2, 1, 0, 1, 2])


def test_intermediate_products_by_hand():
    # row 0 reads B rows 0 and 2 (2 + 3), row 1 reads row 1 (1), row 2
    # reads rows 0, 1, 2 (2 + 1 + 3)
    assert counts.intermediate_products(INDPTR, INDICES, INDPTR) == 5 + 1 + 6


def test_intermediate_products_ignores_capacity_padding():
    padded = np.concatenate([INDICES, [2, 2, 2]])
    assert counts.intermediate_products(INDPTR, padded, INDPTR) == 12


def test_product_counts_by_hand():
    c = counts.product_counts(3, 6, 6, 7, 12, 4)
    # A and B: 4 indptr words and 6 (id, value) pairs each; C: 4 words and 7 pairs
    assert c["bytes"] == (16 + 48) + (16 + 48) + (16 + 56)
    assert c["flops"] == 24
    assert c["gather_bytes"] == 12 * 8


def test_gcn_step_counts_by_hand():
    c = counts.gcn_step_counts(
        nodes=4, nnz_adj=10, features=8, hidden=6, classes=3, layers=3, topk=2
    )
    # matmuls: layer 0 forward + weight gradient, the others also the input gradient
    assert c["matmul_flops"] == 2 * 384 + 3 * 288 + 3 * 144
    # aggregations: layer 0 at width 8 forward; layers 1, 2 at k = 2 forward and backward
    assert c["flops"] == c["matmul_flops"] + 160 + 80 + 80
    # gather: layer 0's dense rows of 8 floats; past it k = 2 (value, id) pairs
    assert c["gather_bytes"] == 10 * (8 * 4 + 2 * 8 + 2 * 8)


def test_gcn_step_counts_at_ogbn_arxiv_widths():
    c = counts.gcn_step_counts(169_343, 2_848_000, 128, 256, 40, 3, 16)
    forward = 2 * 169_343 * (128 * 256 + 256 * 256 + 256 * 40)
    assert forward == pytest.approx(3.68e10, rel=1e-2)
    assert 2.5 * forward < c["matmul_flops"] < 3 * forward


def test_least_seconds_names_its_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(100.0, 50.0, peaks) == (5.0, "bytes")
    assert counts.least_seconds(1000.0, 50.0, peaks) == (10.0, "flops")


def test_peaks_table_knows_v5e_and_refuses_others():
    v5e = counts.load_peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.load_peaks("TPU v9 imaginary")
