"""``span_share``: the time under the host spans named ``part`` over the
time under those named ``whole``, in percent. Nothing to read where the
trace has no ``whole``; with one and no ``part`` the value is 0, which is
a reading (a program without the span reads 0 too: the spans of ``part``
are the program's, and it opens one only where it does that work)."""


def reduce(run, part: str, whole: str):
    outer = run.spans(whole)
    total = sum(e - s for _, s, e in outer)
    if not total:
        return None
    return 100.0 * sum(e - s for _, s, e in run.spans(part)) / total
