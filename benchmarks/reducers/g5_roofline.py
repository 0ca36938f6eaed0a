"""``g5_roofline``: the least time the chip could take for one search over
the kernel time measured for one, in percent. A breadth-first search does
a compare and a bit for every four bytes of adjacency it reads, so by the
count it is bound by memory bandwidth (``peaks.json``'s
``hbm_bytes_per_s``).

The count is the PROBLEM's, the bytes no top-down search avoids, the same
whatever implements it: 8 bytes an input edge tuple of the traversed
component (its two directed entries read once, 4 bytes each) plus 4 bytes
a reached vertex (its parent written once). It comes from the reference's
component (``component_edges`` and ``reached_by_reference``, written onto
the records by the driver's check), not from the program's counters. The
program reads more than that (a block is 128 entries whatever the vertex's
degree, a table row and a queue row a reached vertex); none of it is
counted, so the share cannot pass 100 %. A search that examines FEWER
entries than the component has (a bottom-up step skips a vertex's list at
its first reached neighbour) would make the count too high: a
``benchmark`` issue has to re-make it before such a search claims.
"""

from ..reduce import device_time_per_count


def least_bytes(component_edges: int, reached: int) -> int:
    return 8 * component_edges + 4 * reached


def reduce(run, span: str, pattern: str, peak: str):
    held = [r for r in run.records if r.get("component_edges")]
    kernel_s = device_time_per_count(run, span, pattern, "span", 1e-9)
    if not held or kernel_s is None:  # no reference count, span or kernel
        return None
    least = sum(
        least_bytes(r["component_edges"], r["reached_by_reference"])
        for r in held
    ) / len(held)
    return 100.0 * least / run.peaks[peak] / kernel_s
