"""Host time of the gateway's combining drains (``repro.drain``, begun in
the window: admission, packing, the applier's dispatch, publishing and the
replies), per update the applier applied in the window (its counters);
the device's share of the same updates is ``applier_us_per_update``."""
from jsdoop_bench import spans


def read(run):
    s = spans.of(run)
    applied = run.counter_delta("applied")
    if s is None or not applied:
        return None
    d = spans.durations_ms(s, "repro.drain", spans.window_ns(run))
    return float(d.sum()) * 1e3 / applied if len(d) else None
