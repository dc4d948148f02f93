"""1 - the union of the device's operation intervals over the traced
window, averaged over the chips, in %."""
from ..harness import xplane


def read(spec, obs):
    if obs.get("trace") is None:
        return None
    busy_s, window_s = xplane.busy_seconds(obs["trace"])
    return 100.0 * (1.0 - busy_s / window_s)
