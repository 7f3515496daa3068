"""95th percentile, over every ticket leased in the window, of the time
from the lease request to the volunteer holding the ``SubmitUpdate``
reply. Stale replies count."""
from jsdoop_bench.stats import percentile


def read(run):
    waits = [t.t_reply - t.t_lease for t in run.window_tickets]
    return percentile(waits, 95) * 1e3 if waits else None
