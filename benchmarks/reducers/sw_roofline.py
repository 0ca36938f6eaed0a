"""``sw_roofline``: the least time the chip could take for one alignment
over the kernel time measured for one, in percent. The kernel does no MXU
work and moves 4 MB of boundaries, so by the count it is bound by the
VPU's 32-bit integer rate (``peaks_vpu.json``).

The count is the recurrence as the configuration writes it, nothing
folded, for each of ``n`` x ``m`` cells: one compare of the two letters,
one select of ``match`` or ``mismatch``, one add to the diagonal, two
subtractions of the gap (from above, from the left), three maxima (of
those three and 0) and one more for the running best: 9. The engine's own
row sweep spends more than that a cell (seven shift-and-max steps of a
prefix scan over 128 lanes in the gap chain's place, masks, the boundary
columns), so the share cannot pass 100 %.

What the share cannot show is this deployment's real ceiling: the 127
waves of the wavefront wait for one another, and a wave of a few tiles
fills a sliver of the VPU. The least time by the count is 0.1 ms at
8192 x 8192; the chain takes tens of milliseconds.
"""

import json
import os

from ..reduce import device_time_per_count

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "peaks_vpu.json")

OPS_PER_CELL = {"compare": 1, "select": 1, "add": 1, "subtract": 2,
                "maximum": 3, "running_best": 1}


def cell_update_ops() -> int:
    return sum(OPS_PER_CELL.values())


def least_seconds(cfg: dict, kind: str, peak: str) -> float:
    """The recurrence's operations over the peak of the device kind
    ``kind``; a kind the table lacks is an error, not a default."""
    with open(PEAKS) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise RuntimeError(f"device kind {kind!r} has no row in {PEAKS}")
    return cfg["n"] * cfg["m"] * cell_update_ops() / peaks[kind][peak]


def reduce(run, span: str, pattern: str, peak: str):
    kernel_s = device_time_per_count(run, span, pattern, "span", 1e-9)
    if kernel_s is None:  # no such span or no such kernel in the trace
        return None
    import jax

    least_s = least_seconds(run.cfg, jax.devices()[0].device_kind, peak)
    return 100.0 * least_s / kernel_s
