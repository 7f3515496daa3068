"""Share of the window in which the gateway's dispatch lock was held
(``repro.lock_held``, each hold clipped to the window): the gateway's
serial part, which every request and drain waits behind."""
from jsdoop_bench import spans


def read(run):
    s = spans.of(run)
    if s is None or "repro.lock_held" not in s:
        return None
    return 100.0 * spans.clipped_s(s, "repro.lock_held",
                                   spans.window_ns(run)) / run.window_s
