"""A profiler trace WITH its events' stats, for the readers that need more
than names. `harness/xplane.load` keeps (name, start, duration) only, and
`jax.profiler.ProfileData`, which it reads through, shows an event's own
stats but not those of its metadata, which is where a TPU trace keeps
what describes an operation: on a v5e trace the metadata of every
"XLA Ops" event carries `tf_op`, the operation's `op_name` (its JAX scope
path, where a `jax.named_scope` shows), while the event itself carries
only its device offsets. So this file decodes the `.xplane.pb` itself:
the XSpace message of tsl/profiler/protobuf/xplane.proto, a few nested
messages of varints and strings, with nothing but the standard library.

An event here is (name, start_ns, dur_ns, stats) on the clock of
`xplane.load` (`xplane.line_events` and `device_planes` read this table
too); `stats` merges the metadata's and the event's, cut to the names in
`keep`. A host span of `jax.profiler.TraceAnnotation` carries its
keyword arguments as stats (the batcher tags `serve.tick` with `seq` and
`mode`). Loaded once per run and kept on `obs`.
"""
from __future__ import annotations

import struct
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from ..harness import xplane

Event = Tuple[str, int, int, Dict[str, Any]]
KEEP = ("tf_op", "seq", "mode")
DEVICE_LINES = (xplane.OPS_LINE, xplane.MODULES_LINE)


def _fields(buf: bytes, pos: int, end: int) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: a varint's value,
    a fixed 64- or 32-bit field's raw bytes, or the (start, end) of a
    length-delimited field inside `buf`."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        num, wire = key >> 3, key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield num, 0, val
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield num, 2, (pos, pos + n)
            pos += n
        elif wire == 1:
            yield num, 1, buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            yield num, 5, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {pos}")


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, span, stat_names) -> Tuple[Optional[str], Any]:
    """One XStat: (its name, its value); a ref_value is another stat
    metadata's name."""
    name, val = None, None
    for num, wire, v in _fields(buf, *span):
        if num == 1:
            name = stat_names.get(v)
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _int64(v)
        elif num == 5:
            val = buf[v[0]:v[1]].decode("utf-8", "replace")
        elif num == 6:
            val = bytes(buf[v[0]:v[1]])
        elif num == 7:
            val = stat_names.get(v)
    return name, val


def _map_entry(buf, span) -> Tuple[int, Tuple[int, int]]:
    key, val = 0, (0, 0)
    for num, _, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane(buf, span, keep, device_lines) -> Optional[Dict[str, Any]]:
    name, lines, metas, stat_spans = "", [], [], []
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = buf[v[0]:v[1]].decode()
        elif num == 3:
            lines.append(v)
        elif num == 4:
            metas.append(v)
        elif num == 5:
            stat_spans.append(v)
    is_device = bool(xplane.DEVICE_PLANE.match(name))
    if not (is_device or name.startswith("/host:")):
        return None
    stat_names: Dict[int, str] = {}
    for sp in stat_spans:
        key, val = _map_entry(buf, sp)
        for num, _, v in _fields(buf, *val):
            if num == 2:
                stat_names[key] = buf[v[0]:v[1]].decode()
    meta: Dict[int, Tuple[str, Dict[str, Any]]] = {}
    for sp in metas:
        key, val = _map_entry(buf, sp)
        ev_name, stats = "", {}
        for num, _, v in _fields(buf, *val):
            if num == 2:
                ev_name = buf[v[0]:v[1]].decode("utf-8", "replace")
            elif num == 5:
                k, x = _stat(buf, v, stat_names)
                if k in keep:
                    stats[k] = x
        meta[key] = (ev_name, stats)
    out_lines = []
    for sp in lines:
        line_name, t0_ns, events = "", 0, []
        for num, _, v in _fields(buf, *sp):
            if num == 2:
                line_name = buf[v[0]:v[1]].decode()
            elif num == 3:
                t0_ns = _int64(v)
            elif num == 4:
                events.append(v)
        if is_device and line_name not in device_lines:
            continue
        evs: List[Event] = []
        for esp in events:
            mid = off_ps = dur_ps = 0
            own = None
            for num, _, v in _fields(buf, *esp):
                if num == 1:
                    mid = v
                elif num == 2:
                    off_ps = _int64(v)
                elif num == 3:
                    dur_ps = _int64(v)
                elif num == 4 and not is_device:
                    k, x = _stat(buf, v, stat_names)
                    if k in keep:
                        own = own or {}
                        own[k] = x
            ev_name, stats = meta.get(mid, ("", {}))
            if own:
                stats = {**stats, **own}
            evs.append((ev_name, t0_ns + off_ps // 1000, dur_ps // 1000,
                        stats))
        out_lines.append({"name": line_name, "events": evs})
    return {"name": name, "lines": out_lines}


def load(path: str, keep: Iterable[str] = KEEP,
         device_lines: Iterable[str] = DEVICE_LINES) -> Dict[str, Any]:
    """The device planes (their lines named in `device_lines`) and the
    host planes of an `.xplane.pb`, every event with those of its own and
    its metadata's stats that `keep` names. A device event's own stats
    (its offsets on the device) are never kept."""
    with open(path, "rb") as f:
        buf = f.read()
    keep, device_lines = set(keep), set(device_lines)
    planes = []
    for num, wire, v in _fields(buf, 0, len(buf)):
        if num == 1 and wire == 2:
            plane = _plane(buf, v, keep, device_lines)
            if plane is not None:
                planes.append(plane)
    return {"planes": planes}


def of_run(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The traced run's table with stats, or None where the run kept no
    trace directory. Cached on `obs` under "trace_stats" (the CPU
    rehearsal puts a recorded table there)."""
    if obs.get("trace_stats") is None:
        if not obs.get("trace_dir"):
            return None
        obs["trace_stats"] = load(xplane.find_xplane(obs["trace_dir"]))
    return obs["trace_stats"]


def host_events(table: Dict[str, Any], prefix: str) -> List[Event]:
    """Host events whose name starts with `prefix`, by start time."""
    out = [tuple(e) for p in table["planes"] if p["name"].startswith("/host:")
           for line in p["lines"] for e in line["events"]
           if e[0].startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def scope_of(stats: Dict[str, Any], scopes: Iterable[str]) -> Optional[str]:
    """The innermost of `scopes` on the operation's scope path, if any."""
    want = set(scopes)
    for part in reversed(str(stats.get("tf_op", "")).rstrip(":").split("/")):
        if part in want:
            return part
    return None
