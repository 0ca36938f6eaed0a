"""``record_ratio``: ``scale`` times one field of the records over another,
each summed over the calls (the program's own counters, as the driver
copied them). Records without them, or a zero divisor, are nothing to
read."""


def reduce(run, num: str, den: str, scale: float):
    if any(num not in r or den not in r for r in run.records):
        return None
    total = sum(r[den] for r in run.records)
    if not total:
        return None
    return scale * sum(r[num] for r in run.records) / total
