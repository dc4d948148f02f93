"""Seconds in which a collective ran and no other operation did on that
chip, over the traced window, in %."""
from ..harness import xplane


def read(spec, obs):
    if obs.get("trace") is None:
        return None
    _, window_s = xplane.busy_seconds(obs["trace"])
    return 100.0 * xplane.exposed_seconds(
        obs["trace"], "|".join(spec["patterns"])) / window_s
