"""``jd_roofline``: the least time the chip could take for one call of
``steps`` time steps over the kernel time measured for one, in percent.
A 5-point sum is one add for every four bytes it moves, so by the count a
step is bound by memory bandwidth (``peaks.json``'s ``hbm_bytes_per_s``).

The count is the bytes no implementation that keeps every step's grid
avoids: every cell of the ``H`` x ``W`` grid read once and written once
A STEP, in the configuration's ``dtype``. The engine reads more than that
(each tile loads an 8-row strip above and below it and a 128-column
strip left and right, 31 % over at (256, 1024) tiles); none of it is
counted, so the share cannot pass 100 %. A change that fuses steps in
VMEM (temporal blocking) moves FEWER bytes than this count: it must have
the count re-made by a ``benchmark`` issue before it claims.
"""

import numpy as np

from ..reduce import device_time_per_count


def least_bytes(cfg: dict) -> int:
    return (cfg["steps"] * 2 * cfg["H"] * cfg["W"]
            * np.dtype(cfg["dtype"]).itemsize)


def reduce(run, span: str, pattern: str, peak: str):
    kernel_s = device_time_per_count(run, span, pattern, "span", 1e-9)
    if kernel_s is None:  # no such span or no such kernel in the trace
        return None
    return 100.0 * least_bytes(run.cfg) / run.peaks[peak] / kernel_s
