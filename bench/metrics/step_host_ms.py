"""Mean host time of one volunteer gradient (``repro.step``, begun in the
window): its mini-batch, the jitted call with the model's copy to the
device, and the wait for its loss; the device's share is
``grad_device_ms``."""
from jsdoop_bench import spans


def read(run):
    s = spans.of(run)
    if s is None:
        return None
    d = spans.durations_ms(s, "repro.step", spans.window_ns(run))
    return float(d.mean()) if len(d) else None
