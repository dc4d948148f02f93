"""Pad tokens over bucket tokens in the window's prefill ticks, in %: the
batcher's `prefill_pad_tokens` counter over the rows x bucket of every
standalone and fused prefill tick the flight recorder holds."""


def read(spec, obs):
    bucket_tokens = 0
    for r in obs.get("flight") or []:
        if r["mode"] == "prefill":
            bucket_tokens += r["bucket"] * r["group_pad"]
        elif r["mode"] == "fused":
            bucket_tokens += r["bucket"] * r["rows"]
    if not bucket_tokens:
        return None
    return 100.0 * obs["counters"]["prefill_pad_tokens"] / bucket_tokens
