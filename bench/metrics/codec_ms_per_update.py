"""Host time of the wire codecs, both ends (``repro.encode`` and
``repro.decode``, begun in the window, volunteers and gateway alike), per
commit the volunteers received in the window."""
from jsdoop_bench import spans


def read(run):
    s = spans.of(run)
    commits = run.window_commits
    if s is None or not commits:
        return None
    w = spans.window_ns(run)
    total = sum(float(spans.durations_ms(s, name, w).sum())
                for name in ("repro.encode", "repro.decode"))
    return total / commits if total else None
