"""``uts_lane_share``: of the lane-steps the kernel spent, the share that
expanded a node. Each step hashes one child in every lane; a lane that is
starved, or waits for the slowest lane of its refill round, hashes for
nothing. From the program's own exact counters in the records."""


def reduce(run, field: str, steps: str):
    nlanes = run.cfg["lanes"][0] * run.cfg["lanes"][1]
    lane_steps = sum(r[steps] for r in run.records) * nlanes
    if not lane_steps:
        return None
    return 100.0 * sum(r[field] for r in run.records) / lane_steps
