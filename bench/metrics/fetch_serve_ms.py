"""Mean time the gateway spent serving one ``FetchModel`` under its
dispatch lock (``repro.serve`` of that type, begun in the window):
materializing the version, moving it to the host, encoding and sending
it."""
from jsdoop_bench import spans


def read(run):
    s = spans.of(run)
    if s is None:
        return None
    d = spans.durations_ms(s, "repro.serve", spans.window_ns(run),
                           type="FetchModel")
    return float(d.mean()) if len(d) else None
