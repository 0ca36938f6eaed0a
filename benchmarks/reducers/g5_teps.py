"""``g5_teps``: Graph500's traversed edges per second. For every search
the check held to the reference: the input edge tuples inside the
traversed component (``component_edges``, the reference's count, written
onto the record by the driver's check) over the search's wall time; the
searches' harmonic mean, as the specification gives it. Records without
the count are nothing to read."""


def reduce(run):
    held = [r for r in run.records if r.get("component_edges")]
    if not held or any(r["wall_s"] <= 0 for r in held):
        return None
    return len(held) / sum(r["wall_s"] / r["component_edges"] for r in held)
