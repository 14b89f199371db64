"""The bytes a superstep stage must move, from shapes alone, and the share
of its roofline a measured device time reaches.

Only the stage's necessary traffic counts, so the same work is read
whatever implements it: the csr_spmv kernel's one-hot matmul flops, its
class channel and its padding to whole tiles are the implementation's,
not the stage's. Every value is 4 bytes (int32 keys and indices, float32
values and payloads).
"""
from __future__ import annotations

WORD = 4


def edge_gather_bytes(slots: int, vertices: int, channels: int) -> int:
    """The edge gather: per slot, its source index read and the gathered
    value channels written; plus the value table read once."""
    return WORD * (slots * (1 + channels) + vertices * channels)


def sender_fold_bytes(rows: int, segments: int, channels: int) -> int:
    """The sender combine's segmented fold over the dst-sorted stream:
    each row's key and payload channels read, each segment's key and
    folded channels written."""
    return WORD * (rows + segments) * (1 + channels)


def roofline_pct(need_bytes: int, device_s: float, hbm_bytes_per_s: float):
    """Least time for ``need_bytes`` at peak bandwidth over the measured
    device time, in percent; None where nothing was measured. Neither
    stage does arithmetic that counts, so bandwidth bounds both."""
    if device_s <= 0:
        return None
    return 100.0 * need_bytes / hbm_bytes_per_s / device_s
