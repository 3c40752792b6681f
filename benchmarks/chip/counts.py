"""The work a problem needs, counted from its shapes: the yardstick's side of
every roofline and MFU share.

Nothing here reads the program's plans or padded shapes.  A share computed
from these counts can therefore not pass 100% unless the time measured
leaves out part of the work, and no change to the program's padding or
tiling can make a count stale.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

INDEX_BYTES = 4  # int32 indptr and column ids
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def intermediate_products(a_indptr, a_indices, b_indptr) -> int:
    """IP of C = A @ B: over A's stored entries (i, k), the length of row k
    of B (Algorithm 1's count)."""
    a_indptr = np.asarray(a_indptr, np.int64)
    nnz = int(a_indptr[-1])
    b_len = np.diff(np.asarray(b_indptr, np.int64))
    return int(b_len[np.asarray(a_indices)[:nnz]].sum())


def csr_bytes(n_rows: int, nnz: int, value_bytes: int) -> int:
    """Bytes of one CSR operand: indptr, then a column id and a value per
    stored entry."""
    return (n_rows + 1) * INDEX_BYTES + nnz * (INDEX_BYTES + value_bytes)


def product_counts(n_rows: int, nnz_a: int, nnz_b: int, nnz_c: int, ip: int, value_bytes: int):
    """Compulsory work of one C = A @ B (square operands): A and B read once,
    C written once, 2 FLOP (a multiply and an add) per intermediate product,
    and the gather's useful bytes, a column id and a value of B per
    intermediate product."""
    return {
        "ip": ip,
        "flops": 2 * ip,
        "bytes": csr_bytes(n_rows, nnz_a, value_bytes)
        + csr_bytes(n_rows, nnz_b, value_bytes)
        + csr_bytes(n_rows, nnz_c, value_bytes),
        "gather_bytes": ip * (INDEX_BYTES + value_bytes),
    }


def gcn_layer_widths(features: int, hidden: int, classes: int, layers: int):
    """(input width, output width) of each layer's dense matmul."""
    dims = [features] + [hidden] * (layers - 1) + [classes]
    return list(zip(dims[:-1], dims[1:]))


def gcn_step_counts(
    nodes: int, nnz_adj: int, features: int, hidden: int, classes: int, layers: int, topk: int
):
    """Model FLOPs and the aggregations' gather bytes of one full-batch GCN
    training step, h <- A_hat . TopK(h) . W per layer (layer 0 aggregates
    the dense input features).

    Matmuls: forward 2·n·d_in·d_out per layer; backward the weight gradient
    (the same again) for every layer and the input gradient for every layer
    but the first, whose input is data.  Aggregations: 2 FLOP per stored
    entry of A_hat and per column the layer needs: the input width for
    layer 0, the k kept entries of a TopK row otherwise, forward and (for
    layers past the first) backward.  Nothing recomputed is counted.

    Gather bytes: what the forward aggregations need of the rows of h, once
    per stored entry of A_hat: layer 0's dense row, 4 bytes per input
    column; past it the k kept entries of a TopK row, a value and a column
    id (4 + 4 bytes) each.  The zeros that a dense layout of a TopK row
    carries are not counted.
    """
    widths = gcn_layer_widths(features, hidden, classes, layers)
    matmul = 0
    agg = 0
    gather_bytes = 0
    for layer, (d_in, d_out) in enumerate(widths):
        mm = 2 * nodes * d_in * d_out
        matmul += mm * (2 if layer == 0 else 3)
        need = d_in if layer == 0 else min(topk, d_in)
        agg += 2 * nnz_adj * need * (1 if layer == 0 else 2)
        gather_bytes += nnz_adj * (d_in * 4 if layer == 0 else need * (4 + INDEX_BYTES))
    return {"flops": matmul + agg, "matmul_flops": matmul, "gather_bytes": gather_bytes}


def load_peaks(device_kind: str):
    """The chip's published peaks; a device the table lacks is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


# Device operations of the AIA gather kernels (their Pallas names).
AIA_KERNELS = r"aia_gather_rows|aia_ranged_gather"


def gather_roofline(ctx):
    """Useful gather bytes of the traced window at the chip's HBM bandwidth
    over the device seconds of the AIA kernels, in percent; None when the
    trace holds no such kernel."""
    seconds = ctx["trace"].kernel_s(AIA_KERNELS)
    if seconds <= 0:
        return None
    nbytes = ctx["counts"]["gather_bytes"] * ctx["items"]
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / seconds
