"""Updates the gateway applied per drain in the window, from the
applier's counters: a drain of two or more counts once in ``batches``,
one applied alone counts once as itself."""


def read(run):
    applied = run.counter_delta("applied")
    drains = run.counter_delta("batches") + applied \
        - run.counter_delta("batched_updates")
    return applied / drains if drains else None
