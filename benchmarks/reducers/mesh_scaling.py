"""``mesh_scaling``: of ``chips`` times what one chip does with the same
forest, the share the mesh delivers. It stands where a kernel's roofline
share would: a scheduler has no flop count, and its ceiling is 100. The
twin is whole calls on one device, the median of several timed in set-up
by the driver. The share is given as computed: one above 100 says the twin
was mistimed or the work miscounted, and is there to be seen."""

import statistics


def reduce(run, work: str, wall: str, twin_work: str, twin_wall: str):
    rates = [r[work] / r[wall] for r in run.records if r[wall] > 0]
    if not rates or not run.records[0].get(twin_wall):
        return None
    twin = run.records[0][twin_work] / run.records[0][twin_wall]
    return 100.0 * statistics.median(rates) / (run.cfg["chips"] * twin)
