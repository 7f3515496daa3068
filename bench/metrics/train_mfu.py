"""Model FLOPs of the updates committed in the traced window (forward and
backward of each mini-batch gradient, from the shapes; recomputation and
rejected gradients left out), per second of that window, over the chip's
published bf16 peak."""


def read(run):
    if run.trace is None or not run.trace_commits or run.peaks is None:
        return None
    t0, t1 = run.trace_window
    rate = run.flops_per_update * run.trace_commits / (t1 - t0)
    return 100.0 * rate / run.peaks["bf16_flops_per_s"]
