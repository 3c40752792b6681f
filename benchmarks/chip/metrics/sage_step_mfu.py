"""sage_step_mfu: model FLOPs of the GraphSAGE training steps of the traced
window (both paths' matmuls forward and backward, and the aggregations'
required work; nothing recomputed; ``sage_counts.py``) over its seconds, as
a share of the chip's peak, read as ``gcn_step_mfu`` reads the GCN's."""

from workload import load

read = load("metrics", "gcn_step_mfu").read
