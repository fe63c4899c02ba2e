"""The work an operation needs, from its shapes, whatever kernel implements it.

Roofline shares count these bytes, not what today's kernels move: a kernel that
sweeps more than the operation needs reads as a small share, and a later kernel
that needs less cannot read above 100%.
"""

from __future__ import annotations

INT32 = 4


def probe_bytes(n: int, m: int) -> int:
    """Match ranges of n sorted keys in m sorted keys: both key arrays read once,
    a (lower, upper) pair written per key of the first."""
    return INT32 * (n + m) + 2 * INT32 * n


def pair_expansion_bytes(cap_out: int) -> int:
    """Match ranges expanded into cap_out (a_idx, b_idx) pairs: the pairs written."""
    return 2 * INT32 * cap_out


def partition_pack_bytes(n: int) -> int:
    """n keys hash-partitioned: keys read, a partition id and a slot written per key."""
    return INT32 * n + 2 * INT32 * n


def least_time_s(n_bytes: float, peaks: dict) -> float:
    """The least time the chip's memory needs to move ``n_bytes``."""
    return n_bytes / float(peaks["hbm_bytes_per_s"])
