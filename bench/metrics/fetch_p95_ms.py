"""95th percentile of the host time of every ``FetchModel`` call made in
the window: the gateway materializing a published version, encoding it
(parameters and optimizer state), the wire, and decoding."""
from jsdoop_bench.stats import percentile


def read(run):
    calls = run.window_calls("FetchModel")
    return percentile([c.dt for c in calls], 95) * 1e3 if calls else None
