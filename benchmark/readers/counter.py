"""One of the counts the runner took from the program."""


def read(spec, obs):
    return obs.get("counters", {}).get(spec["counter"])
