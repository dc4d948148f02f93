"""Percentile of the engine's own queue wait, admit_time - submit_time of
each request handle (the engine's clock), in ms."""
from ..harness import stats


def read(spec, obs):
    waits = obs.get("queue_waits_s") or []
    p = stats.percentile(waits, float(spec.get("percentile", 90)))
    return None if p is None else p * 1e3
