"""Share of the time the device had work to do in which it waited for the
host, in %, over the WHOLE window: from the flight records alone. Over
consecutive ticks of which the earlier one synced and closed with slots
still decoding (`live_after` > 0: there was a next step to run), the time
from the earlier tick's read-back returning (`t_synced`) to the later
tick's call being issued (`t_dispatch`): commit, retire, admission, the
engine's delivery and housekeeping, packing. As a share of that plus the
earlier ticks' time from issue to read-back (`dispatch_s + wait_s`)."""


def read(spec, obs):
    recs = sorted((r for r in obs.get("flight") or [] if r.get("closed")),
                  key=lambda r: r["seq"])
    gap = busy = 0.0
    for a, b in zip(recs, recs[1:]):
        if b["seq"] != a["seq"] + 1 or not a.get("synced") \
                or not a.get("live_after") or b.get("t_dispatch") is None:
            continue
        gap += max(0.0, b["t_dispatch"] - a["t_synced"])
        busy += a["dispatch_s"] + a["wait_s"]
    if not busy:
        return None
    return 100.0 * gap / (gap + busy)
