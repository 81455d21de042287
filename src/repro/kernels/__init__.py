# Pallas TPU kernels for the perf-critical compute layers, each as
# <name>/ {<name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper),
# ref.py (pure-jnp oracle)} — validated in interpret mode on CPU:
#   degree_count  — the paper's §5.1 calibration histogram (one-hot MXU tiles)
#   spmv          — PR-pull / GNN sum-aggregation (row-split dst-tiled COO, owner-computes)
#   scoring       — two-tower candidate scoring + hierarchical top-k
#   embedding_bag — scalar-prefetch gather + revisit-accumulate bag reduce
#   attention     — causal flash attention fwd (online softmax, VMEM scratch)
from . import degree_count, spmv, scoring, embedding_bag, attention
