"""A served step program's device time by scope, from a trace that
`benchmark/tools/dump_stats.py` kept: for each kind of `jit_serve_*`
program in the trace, its MEDIAN program's exclusive device time by the
innermost `jax.named_scope` of the served block on each operation's scope
path (`tf_op` of the event's metadata, `benchmark/readers/xstats.py`;
"unscoped": the compiler's own copies and `lax.scan`'s weight slices,
which carry none), and under it the largest operations of the scopes named
with `--ops`. It reads a file: no chip, no JAX (PERF.md section 5's
tables, PR 34 and PR 39).

Usage: python tools/chunk_by_scope.py chiprun_out/<out> [--ops unscoped,kv_pool_write]
"""
import argparse
import collections
import gzip
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.getcwd())

SCOPES = ("embed", "attn_qkv", "kv_pool_write", "attn_kernel", "attn_out",
          "attn_window", "attn_full", "mla_q", "mla_kv_latent", "mla_absorb",
          "mlp", "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          "moe_shared", "lm_head", "sample",
          "kv_pool_read")           # in traces of trees before PR 39 alone


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out", help="a directory dump_stats.py wrote")
    ap.add_argument("--ops", default="unscoped,kv_pool_write,attn_kernel",
                    help="scopes whose largest operations are listed")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    from benchmark.harness import xplane
    from benchmark.readers import xstats

    with tempfile.TemporaryDirectory() as tmp:
        raw = os.path.join(args.out, "trace.xplane.pb")
        if not os.path.exists(raw):
            raw = os.path.join(tmp, "trace.xplane.pb")
            with gzip.open(os.path.join(args.out, "trace.xplane.pb.gz"),
                           "rb") as src, open(raw, "wb") as dst:
                shutil.copyfileobj(src, dst)
        table = xstats.load(raw)
    dev = xplane.device_planes(table)[0]
    ops = sorted(xplane.line_events(dev, xplane.OPS_LINE),
                 key=lambda e: (e[1], -e[2]))
    # same order in, same order out: leaf_exclusive sorts by this key
    exclusive = xplane.leaf_exclusive([e[:3] for e in ops])
    kinds = collections.defaultdict(list)
    for name, start, dur, _ in xplane.line_events(dev, xplane.MODULES_LINE):
        if name.startswith("jit_serve"):
            kinds[name.split("(")[0]].append((dur, start))
    listed = set(args.ops.split(","))
    for kind, programs in sorted(kinds.items()):
        dur, start = sorted(programs)[len(programs) // 2]
        by, names = collections.Counter(), collections.Counter()
        for (name, s, _, stats), (_, _, own) in zip(ops, exclusive):
            if start <= s < start + dur:
                scope = xstats.scope_of(stats, SCOPES) or "unscoped"
                by[scope] += own
                if scope in listed:
                    names[scope, name[:110]] += own
        print(f"== {kind}: {len(programs)} programs, median "
              f"{dur / 1e6:.2f} ms")
        for scope, t in by.most_common():
            print(f"   {scope:14s} {t / 1e6:8.2f} ms {100 * t / dur:5.1f}%")
        for (scope, name), t in names.most_common(args.top):
            print(f"      [{scope}] {t / 1e6:7.3f} ms  {name}")


if __name__ == "__main__":
    main()
