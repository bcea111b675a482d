"""Bytes the checkpoint cells' kernels have to move, computed from the
state's shapes, beside ``bench/kernel_bytes.py``: a kernel's roofline share
is these bytes over the chip's peak bandwidth, divided by the device time
of its whole jitted program."""
from __future__ import annotations


def copy_crc(snapshot_bytes: int) -> int:
    """Every leaf snapshotted is read once, for its copy and its CRC at
    once, and its copy written once."""
    return 2 * int(snapshot_bytes)
