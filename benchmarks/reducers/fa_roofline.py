"""``fa_roofline``: the least time the chip could take for one sweep over
the kernel time measured for one, in percent. A 5-point sum is one add
for every four bytes it moves, so by the count the sweep is bound by
memory bandwidth (``peaks.json``'s ``hbm_bytes_per_s``).

The count is the bytes no implementation avoids: every cell of the
``H`` x ``W`` grid read once and written once, in the configuration's
``dtype``. The engine reads more than that (each tile loads the aligned
superset around it, 16 % over at (256, 1024) tiles), and the halo's zeros
besides; none of it is counted, so the share cannot pass 100 %.
"""

import numpy as np

from ..reduce import device_time_per_count


def least_bytes(cfg: dict) -> int:
    return 2 * cfg["H"] * cfg["W"] * np.dtype(cfg["dtype"]).itemsize


def reduce(run, span: str, pattern: str, peak: str):
    kernel_s = device_time_per_count(run, span, pattern, "span", 1e-9)
    if kernel_s is None:  # no such span or no such kernel in the trace
        return None
    return 100.0 * least_bytes(run.cfg) / run.peaks[peak] / kernel_s
