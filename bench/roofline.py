"""Operations and bytes the benchmark's kernels must at least move.

Counted from shapes alone, independent of how the program computes them,
so that a roofline share says how far a kernel is from the chip's limit.
"""
from __future__ import annotations

F32 = 4


def dpm_merge_bytes(batch: int, nodes: int, candidates: int = 24) -> int:
    """Least HBM bytes of one Algorithm 1 dispatch over ``batch``
    instances on a fabric of ``nodes``: each instance's destination mask
    (1 byte a node), wedge membership row (int32) and source index read
    once; the dense pairwise tables it prices with (route distance,
    unicast price, high- and low-channel label-chain prices, float32 or
    int32, ``nodes`` x ``nodes`` each) and the node labels and label order
    read once; per candidate, the chosen flag, pick order, representative
    and mode written once."""
    per_instance = nodes * (1 + F32) + F32 + candidates * (1 + F32 + F32 + 1)
    tables = 4 * nodes * nodes * F32 + 2 * nodes * F32
    return batch * per_instance + tables


def xsim_cycle_bytes(batch: int, links: int, vcs: int, depth: int,
                     nodes: int) -> int:
    """Least HBM bytes of one simulated cycle of a batch: every (link, VC)
    FIFO's ``depth`` flit slots, its owner and credit count, each node's
    two NI lane fronts, and each link's flit counter, all int32, read and
    written once."""
    state = links * vcs * (depth + 2) + 2 * nodes + links
    return 2 * batch * state * F32
