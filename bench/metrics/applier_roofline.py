"""The applier's share of its roofline: the least time the chip could
take for the bytes one update must move (see the family's
``applier_bytes_per_update``), at the published HBM bandwidth, over the
device time the applier's programs took per commit. Memory bounds it: the
update's arithmetic is a few operations per word."""


def read(run):
    if run.trace is None or not run.trace_commits or run.peaks is None:
        return None
    seconds = run.trace.layer_device_s("applier")
    if not seconds:
        return None
    least = run.applier_bytes_per_update / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / run.trace_commits)
