"""Bytes each kernel's algorithm has to move, computed from shapes.  A
kernel's roofline share is these bytes over the chip's peak bandwidth,
divided by the device time of its whole jitted program."""
from __future__ import annotations

import math
from typing import Sequence


def batch_copy(n_pages: int, page_shape: Sequence[int], itemsize: int) -> int:
    """Every page copied is read once and written once."""
    return 2 * int(n_pages) * math.prod(page_shape) * int(itemsize)
