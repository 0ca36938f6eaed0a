"""``mesh_balance``: how evenly the stolen forest spread: the least busy
device's executed count over the busiest's, in percent, mean over the
calls. From the program's own exact per-device counters in the records."""


def reduce(run, field: str):
    shares = [100.0 * min(r[field]) / max(r[field]) for r in run.records
              if r.get(field) and max(r[field]) > 0]
    return sum(shares) / len(shares) if shares else None
