# Pallas TPU kernels for the compute hot-spots of the join data plane + the models:
#   merge_join.py      — sorted-key join probe and pair expansion (merge-path tile schedule)
#   hash_partition.py  — multiplicative hash + per-tile radix histogram
#   ssd.py             — Mamba-2/SSD intra-chunk masked matmul + state update
#   flash_attention.py — online-softmax attention (the dominant memory term's fix)
# ops.py holds the jit'd public wrappers (interpret=True on CPU, compiled on TPU);
# ref.py holds the pure-jnp oracles every kernel is allclose-tested against.
from .ops import flash_attention, hash_partition, merge_join_counts, ssd_chunk
