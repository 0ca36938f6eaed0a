"""``steal_rows``: descriptor rows that crossed between chips in one call:
the rows every device installed from the steal exchange's inboxes, summed,
mean over the calls. From the kernel's own counters in the records."""


def reduce(run, field: str):
    rows = [sum(r[field]) for r in run.records]
    return sum(rows) / len(rows) if rows else None
