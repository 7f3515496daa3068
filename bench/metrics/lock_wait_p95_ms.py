"""95th percentile of the gateway's waits for its dispatch lock
(``repro.lock_wait``) begun in the window: every request, drain and sweep
takes that lock before it touches the endpoint."""
from jsdoop_bench import spans
from jsdoop_bench.stats import percentile


def read(run):
    s = spans.of(run)
    if s is None:
        return None
    waits = spans.durations_ms(s, "repro.lock_wait", spans.window_ns(run))
    return percentile(list(waits), 95) if len(waits) else None
