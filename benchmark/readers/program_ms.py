"""Device duration, in ms, of the programs whose name in the device trace's
per-program line matches one of the metric's patterns: their median, or
the nearest-rank `percentile` that the metric's file names."""
from ..harness import stats, xplane


def read(spec, obs):
    if obs.get("trace") is None:
        return None
    ms = xplane.program_durations_ms(obs["trace"], "|".join(spec["patterns"]))
    if "percentile" in spec:
        return stats.percentile(ms, float(spec["percentile"]))
    return stats.median(ms)
