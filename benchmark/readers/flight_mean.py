"""Mean of one field of the batcher's flight-recorder records over the
window's ticks of the listed modes (host-side counts, not device time)."""


def read(spec, obs):
    modes = set(spec["modes"])
    vals = [r[spec["field"]] for r in obs.get("flight") or []
            if r["mode"] in modes and spec["field"] in r]
    return sum(vals) / len(vals) if vals else None
