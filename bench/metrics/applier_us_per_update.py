"""Device time of the applier's programs, as the model family names them
(the scan of a drain and the slices that materialize versions for fetches,
or the delta adds), per commit in the traced window."""


def read(run):
    if run.trace is None or not run.trace_commits:
        return None
    seconds = run.trace.layer_device_s("applier")
    return seconds / run.trace_commits * 1e6 if seconds else None
