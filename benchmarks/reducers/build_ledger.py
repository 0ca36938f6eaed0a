"""``build_ledger``: one field of the program's build ledger
(``hclib_tpu.runtime.progcache.build_ledger()``: what JAX's own
monitoring stamped around every trace, lowering and backend compile of
the process, each thread's outermost spans only) summed over its rows.

It is read in the run's own process, when the line is made, and not from
the records: a driver's record carries what that driver copies and no
driver may be edited to copy more. So the sum is the whole process's up
to then: set-up (the driver's own ``jnp`` passes that make the inputs
included, as ``setup_s`` includes them), the window, where
``window_builds`` says there was none, and whatever the check behind the
window compiled. A program from before the ledger is nothing to read."""


def reduce(run, field: str):
    from hclib_tpu.runtime import progcache

    ledger = getattr(progcache, "build_ledger", None)
    if ledger is None:
        return None
    return sum(row[field] for row in ledger())
