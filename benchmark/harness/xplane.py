"""From a profiler trace to numbers. `load` turns an .xplane.pb into a
plain table (planes -> lines -> events of name, start_ns, dur_ns); every
reduction below works on that table, so the tests check them on a small
recorded table (benchmark/tests/data/) without a chip.

What a TPU v5e trace holds (read by hand, PERF.md section 3): one plane
per chip named "/device:TPU:<n>" whose line "XLA Modules" has one event
per executed program and whose line "XLA Ops" has one event per operation
(nested: a while loop's event spans its body's), and host planes
("/host:CPU") with one line per thread.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Tuple

Event = Tuple[str, int, int]            # name, start_ns, dur_ns
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, host: bool = True) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name)
                or (host and plane.name.startswith("/host:"))):
            continue
        lines = [{"name": line.name,
                  "events": [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                             for ev in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(table: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [p for p in table["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: Dict[str, Any], line_name: str) -> List[Event]:
    out: List[Event] = []
    for line in plane["lines"]:
        if line["name"] == line_name:
            out.extend(tuple(e) for e in line["events"])
    return out


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _spans(events: List[Event]) -> List[Tuple[int, int]]:
    return [(s, s + d) for _, s, d in events]


def window_ns(table: Dict[str, Any]) -> Tuple[int, int]:
    """The traced window on the device clock: from the first to the last
    device event of any chip."""
    starts, ends = [], []
    for p in device_planes(table):
        for line in p["lines"]:
            for _, s, d in line["events"]:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise ValueError("no device event in the trace")
    return min(starts), max(ends)


def busy_seconds(table: Dict[str, Any]) -> Tuple[float, float]:
    """(busy_s, window_s): the union of the intervals in which an
    operation ran, averaged over the chips, and the window's length."""
    planes = device_planes(table)
    w0, w1 = window_ns(table)
    busy = [union_ns(_spans(line_events(p, OPS_LINE))) for p in planes]
    return sum(busy) / len(busy) / 1e9, (w1 - w0) / 1e9


def program_durations_ms(table: Dict[str, Any], pattern: str) -> List[float]:
    """Device durations of the programs whose name matches, first chip."""
    rx = re.compile(pattern)
    ev = line_events(device_planes(table)[0], MODULES_LINE)
    return [d / 1e6 for n, _, d in ev if rx.search(n)]


def program_counts(table: Dict[str, Any], min_ms: float = 1.0) -> str:
    """One line for a run's log: the first chip's programs of `min_ms` or
    more by name (fingerprint dropped), with count and median duration."""
    by: Dict[str, List[float]] = {}
    for n, _, d in line_events(device_planes(table)[0], MODULES_LINE):
        if d >= min_ms * 1e6:
            by.setdefault(n.split("(")[0], []).append(d / 1e6)
    return ", ".join(f"{len(v)} x {n} (median {sorted(v)[len(v) // 2]:.1f} "
                     f"ms)" for n, v in sorted(by.items())) or "none"


def leaf_exclusive(events: List[Event]) -> List[Event]:
    """Events with the time of the events nested wholly inside them taken
    out (an operation line nests a loop's body inside the loop's event;
    an asynchronous operation that merely overlaps the next is no parent)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List] = []                # name, start, exclusive, end
    stack: List[int] = []               # indices into out: open parents
    for name, s, d in evs:
        while stack and out[stack[-1]][3] <= s:
            stack.pop()
        if stack and s + d <= out[stack[-1]][3]:
            out[stack[-1]][2] -= d
        out.append([name, s, d, s + d])
        stack.append(len(out) - 1)
    return [(n, s, max(x, 0)) for n, s, x, _ in out]


def kernel_seconds(table: Dict[str, Any], pattern: str) -> Tuple[float, int]:
    """(seconds, calls) of the operations whose name matches, first chip."""
    rx = re.compile(pattern)
    ev = [e for e in line_events(device_planes(table)[0], OPS_LINE)
          if rx.search(e[0])]
    return sum(d for _, _, d in ev) / 1e9, len(ev)


def exposed_seconds(table: Dict[str, Any], pattern: str) -> float:
    """Seconds, averaged over the chips, in which an operation matching
    `pattern` (collectives) ran and no other operation did."""
    rx = re.compile(pattern)
    out = []
    for p in device_planes(table):
        ev = leaf_exclusive(line_events(p, OPS_LINE))
        coll = [(s, s + d) for n, s, d in ev if rx.search(n) and d > 0]
        rest = [(s, s + d) for n, s, d in ev if not rx.search(n) and d > 0]
        both = union_ns(coll + rest)
        out.append(both - union_ns(rest))
    return sum(out) / len(out) / 1e9 if out else 0.0


def top_ops(table: Dict[str, Any], k: int = 10) -> List[List]:
    """The k operations that took most device time (exclusive), first chip."""
    total: Dict[str, int] = {}
    for n, _, d in leaf_exclusive(line_events(device_planes(table)[0],
                                              OPS_LINE)):
        total[n] = total.get(n, 0) + d
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n[:120], d / 1e9] for n, d in rows]


def idle_gaps(table: Dict[str, Any], k: int = 10) -> List[List]:
    """The k longest gaps between device operations on the first chip,
    each named by the host event that overlaps it most."""
    ev = sorted(_spans(line_events(device_planes(table)[0], OPS_LINE)))
    gaps, end = [], None
    for s, e in ev:
        if end is not None and s > end:
            gaps.append((s - end, end, s))
        end = e if end is None else max(end, e)
    gaps.sort(reverse=True)
    host = [(n, s, s + d) for p in table["planes"]
            if p["name"].startswith("/host:")
            for line in p["lines"] for n, s, d in line["events"]]
    out = []
    for length, g0, g1 in gaps[:k]:
        best, best_ov = "no host event", 0
        for n, s, e in host:
            ov = min(e, g1) - max(s, g0)
            # the event that explains the gap is about its size: skip the
            # thread-long wrappers
            if ov > best_ov and (e - s) <= 4 * length:
                best, best_ov = n, ov
        out.append([best[:120], length / 1e9])
    return out


def cut(table: Dict[str, Any], start_ns: int, end_ns: int) -> Dict[str, Any]:
    """The events wholly inside [start_ns, end_ns): a small table for the
    tests."""
    planes = []
    for p in table["planes"]:
        lines = []
        for line in p["lines"]:
            ev = [list(e) for e in line["events"]
                  if e[1] >= start_ns and e[1] + e[2] <= end_ns]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def summary(table: Dict[str, Any], top: int = 40) -> str:
    """What a trace holds, for reading it by hand: planes, lines, event
    counts, and each device line's names by total time."""
    rows = []
    for p in table["planes"]:
        rows.append(f"plane {p['name']}")
        for line in p["lines"]:
            ev = line["events"]
            rows.append(f"  line {line['name']!r}: {len(ev)} events")
            if not DEVICE_PLANE.match(p["name"]) and len(ev) < 50:
                continue
            total: Dict[str, List[int]] = {}
            for n, _, d in ev:
                t = total.setdefault(n, [0, 0])
                t[0] += d
                t[1] += 1
            for n, (d, c) in sorted(total.items(),
                                    key=lambda kv: -kv[1][0])[:top]:
                rows.append(f"    {d / 1e6:10.3f} ms {c:7d} x  {n[:160]}")
    return "\n".join(rows)


def dump(table: Dict[str, Any], out_dir: str) -> None:
    """summary.txt, timeline.txt and a small cut of the table as gzipped
    JSON: the two consecutive programs of a millisecond or more that are
    shortest together, whole, with names shortened (the tests' recorded
    table is such a cut)."""
    import gzip
    import json
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "summary.txt"), "w") as f:
        f.write(summary(table))
    with open(os.path.join(out_dir, "timeline.txt"), "w") as f:
        f.write(timeline(table))
    mods = sorted((e for e in line_events(device_planes(table)[0],
                                          MODULES_LINE) if e[2] >= 1e6),
                  key=lambda e: e[1])
    if len(mods) < 2:
        return
    first, second = min(zip(mods, mods[1:]),
                        key=lambda ab: ab[1][1] + ab[1][2] - ab[0][1])
    part = cut(table, first[1] - 10**6, second[1] + second[2] + 10**6)
    for p in part["planes"]:
        for line in p["lines"]:
            for e in line["events"]:
                e[0] = e[0][:96]
    with gzip.open(os.path.join(out_dir, "cut.json.gz"), "wt") as f:
        json.dump(part, f)


def timeline(table: Dict[str, Any], limit: int = 60) -> str:
    """The first chip's programs in order, with their offsets into the
    window, and the host's long events: for reading a trace by hand."""
    w0, w1 = window_ns(table)
    rows = [f"window {(w1 - w0) / 1e6:.1f} ms"]
    ev = sorted(line_events(device_planes(table)[0], MODULES_LINE),
                key=lambda e: e[1])
    for n, s, d in ev[:limit]:
        rows.append(f"  +{(s - w0) / 1e6:9.1f} ms {d / 1e6:9.3f} ms  {n[:60]}")
    for p in table["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for line in p["lines"]:
            for n, s, d in sorted(line["events"], key=lambda e: e[1]):
                if d > 20e6:
                    rows.append(f"  host {line['name'][:24]!r} +"
                                f"{(s - w0) / 1e6:9.1f} ms {d / 1e6:9.1f} ms"
                                f"  {n[:60]}")
    return "\n".join(rows)
