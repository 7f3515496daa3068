"""Share of the updates submitted in the window that admission rejected
as too stale, from the applier's counters."""


def read(run):
    applied, rejected = run.counter_delta("applied"), run.counter_delta("rejected")
    total = applied + rejected
    return 100.0 * rejected / total if total else None
