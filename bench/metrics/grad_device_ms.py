"""Device time of the volunteer step's programs, as the model family names
them, per mini-batch gradient begun in the traced window (a LocalSteps
ticket computes k of them, with their local optimizer steps)."""


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.layer_device_s("volunteer step")
    grads = run.trace_gradients
    return seconds / grads * 1e3 if seconds and grads else None
