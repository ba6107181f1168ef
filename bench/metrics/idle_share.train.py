"""Share of the traced window in which no operation ran on the device,
averaged over the devices, in %."""
from harness import trace as TR


def read(out):
    t = out.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - TR.busy_seconds(t) / t.window_s)
