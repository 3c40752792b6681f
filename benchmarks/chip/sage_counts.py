"""The work of one full-batch GraphSAGE training step, counted from the
problem's shapes, as ``counts.py`` counts the GCN's (and from it): the
yardstick of ``sage_step_mfu`` and ``aia_gather_roofline.sage``."""

from __future__ import annotations

from counts import gcn_step_counts


def sage_step_counts(
    nodes: int, nnz_adj: int, features: int, hidden: int, tasks: int, layers: int, topk: int
):
    """Model FLOPs and the aggregations' gather bytes of one step,
    h <- (D^-1 A) . TopK(h) . W_l + b_l + h . W_r per layer (layer 0
    aggregates the dense input features).

    The aggregations and their gather are the GCN's over the same entries:
    2 FLOP per stored entry and needed column (the input width at layer 0,
    the k kept entries of a TopK row past it), forward and, past layer 0,
    backward; gather bytes per stored entry, layer 0's dense row (4 bytes a
    feature) and past it k (value, column id) pairs of 4 + 4 bytes, never
    the 128-lane padding.  The matmuls are twice the GCN's, one per path
    (W_l on the aggregate, W_r on h), each forward, weight gradient, and
    past layer 0 input gradient.  The bias adds (n·d_out a layer) are not
    counted.
    """
    gcn = gcn_step_counts(nodes, nnz_adj, features, hidden, tasks, layers, topk)
    matmul = 2 * gcn["matmul_flops"]
    return {
        "flops": gcn["flops"] - gcn["matmul_flops"] + matmul,
        "matmul_flops": matmul,
        "gather_bytes": gcn["gather_bytes"],
    }
