"""Share of the decoding step programs' device time that goes to the
expert layer, in %: as `kv_pool_copy` (exclusive device time of the
operations whose scope path lies under one of the metric's `scopes`, over
the device time of every program that matches `programs` in the traced
span), and also counting the operations NAMED by one of `patterns`: the
chip's grouped GEMM is a custom call whose event carries no scope path, so
a reader of scopes alone would leave the experts out of the expert
layer."""
from . import xstats
from .moe_expert_roofline import scoped_seconds


def read(spec, obs):
    table = xstats.of_run(obs)
    if table is None:
        return None
    spent, total = scoped_seconds(table, spec)
    if not spent or not total:
        return None             # a program without the scopes: no reading
    return 100.0 * spent / total
