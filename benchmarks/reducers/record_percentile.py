"""``record_percentile``: ``scale`` times the q-th percentile (nearest
rank) over every entry of one list-valued field of the records. Records
without the field are nothing to read."""

import math


def reduce(run, field: str, q: float, scale: float):
    if any(field not in r for r in run.records):
        return None
    vals = sorted(v for r in run.records for v in r[field])
    if not vals:
        return None
    return scale * vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]
