r"""A traced run that keeps what its trace holds WITH the events' stats
(scope paths on device operations, the attributes of host spans) and the
window's flight records: for reading by hand, and for cutting the table
that benchmark/tests/data/ records.

    python3 benchmark/tools/dump_stats.py <out> --workload <cell> \
        --seed 1 --seconds 51

Writes chiprun_out/<out>/: what dump_trace.py writes, stats.txt (per
line, the names that took most time, each with its stats), and
cut_stats.json.gz (some consecutive step programs, whole, with their
operations' scope paths, the host spans over them, and the flight
records of the window) and the trace itself, gzipped.
"""
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run                      # noqa: E402
from benchmark.harness import xplane           # noqa: E402
from benchmark.readers import xstats           # noqa: E402


HOST_SPANS = ("serve.", "engine.", "serving.")     # the program's own


def stats_summary(table, top: int = 25) -> str:
    rows = []
    for p in table["planes"]:
        rows.append(f"plane {p['name']}")
        for line in p["lines"]:
            by = {}
            for n, _, d, st in line["events"]:
                t = by.setdefault(n, [0, 0, st])
                t[0] += d
                t[1] += 1
            rows.append(f"  line {line['name']!r}: {len(line['events'])} "
                        f"events, {len(by)} names")
            for n, (d, c, st) in sorted(by.items(),
                                        key=lambda kv: -kv[1][0])[:top]:
                rows.append(f"    {d / 1e6:10.3f} ms {c:7d} x  {n[:100]}")
                for k, v in st.items():
                    rows.append(f"        {k} = {str(v)[:200]}")
    return "\n".join(rows)


def cut(table, flight, programs: int = 4, keep=("tf_op", "seq", "mode")):
    """`programs` consecutive step programs of a millisecond or more: the
    shortest such run in which the rarer kind of program appears most
    often (two fused and two plain, if the trace has them side by side),
    with every device event wholly inside and the program's own host
    spans round them; names shortened, stats cut to `keep`; beside them
    the flight records."""
    dev = xplane.device_planes(table)[0]
    mods = sorted((e for e in xplane.line_events(dev, xplane.MODULES_LINE)
                   if e[2] >= 1e6), key=lambda e: e[1])
    best = None
    for i in range(len(mods) - programs + 1):
        run_ = mods[i:i + programs]
        kinds = [m[0].split("(")[0] for m in run_]
        rarest = min(kinds.count(k) for k in set(kinds)) \
            if len(set(kinds)) > 1 else 0
        length = run_[-1][1] + run_[-1][2] - run_[0][1]
        key = (-rarest, length)
        if best is None or key < best[0]:
            best = (key, run_)
    if best is None:
        return None
    run_ = best[1]
    # room for the first tick's packing and the last one's commit
    t0, t1 = run_[0][1] - 30 * 10**6, run_[-1][1] + run_[-1][2] + 30 * 10**6
    planes = []
    for p in table["planes"]:
        lines = []
        device = bool(xplane.DEVICE_PLANE.match(p["name"]))
        for line in p["lines"]:
            ev = [[n[:32], s, d, {k: (v[-64:] if isinstance(v, str) else v)
                                  for k, v in st.items() if k in keep}]
                  for n, s, d, st in line["events"]
                  if s >= t0 and s + d <= t1 and (
                      device or n.startswith(HOST_SPANS))]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes, "flight": flight}


if __name__ == "__main__":
    out = os.path.join(ROOT, "chiprun_out", sys.argv[1])
    seen = {}
    layer_values = run.layer_values

    def keep_obs(specs, obs):
        seen["obs"] = obs
        return layer_values(specs, obs)

    run.layer_values = keep_obs
    rc = run.main(sys.argv[2:] + ["--trace", "1"],
                  overrides={"keep_trace": out})
    obs = seen["obs"]
    if not obs.get("trace_dir"):
        # a runner that keeps no trace_dir on obs (train): the trace
        # thread's directory is the newest of its kind
        obs["trace_dir"] = max(glob.glob(os.path.join(
            tempfile.gettempdir(), "bench_trace_*")), key=os.path.getmtime)
    flight = obs.get("flight") or []
    table = xstats.of_run(obs)
    with open(os.path.join(out, "stats.txt"), "w") as f:
        f.write(stats_summary(table))
    with gzip.open(os.path.join(out, "flight.json.gz"), "wt") as f:
        json.dump(flight, f)
    raw = xplane.find_xplane(obs["trace_dir"])
    if os.path.getsize(raw) < 256 << 20:        # gzips to about a tenth
        with open(raw, "rb") as src, gzip.open(
                os.path.join(out, "trace.xplane.pb.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
    part = cut(table, flight)
    if part is not None:
        with gzip.open(os.path.join(out, "cut_stats.json.gz"), "wt") as f:
            json.dump(part, f)
    sys.exit(rc)
