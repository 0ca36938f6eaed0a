"""``record_mean``: ``scale`` times the mean over the calls of one field of
the records (the program's own counter, as the driver copied it). Records
without the field are nothing to read."""


def reduce(run, field: str, scale: float):
    vals = [r.get(field) for r in run.records]
    if not vals or None in vals:
        return None
    return scale * sum(vals) / len(vals)
