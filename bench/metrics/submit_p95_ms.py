"""95th percentile of the host time of every ``SubmitUpdate`` call made in
the window, at the volunteers' transport: encode, wire, the gateway's
combining drain, admission, the applier's dispatch, and the reply."""
from jsdoop_bench.stats import percentile


def read(run):
    calls = run.window_calls("SubmitUpdate")
    return percentile([c.dt for c in calls], 95) * 1e3 if calls else None
