"""From a profiler trace to the numbers the per-layer metrics read.

Two halves.  ``extract`` reads the ``.xplane.pb`` the JAX profiler wrote
and keeps three kinds of events as plain dicts: the device's operations
(line ``XLA Ops`` of each ``/device:`` plane), its executables (line
``XLA Modules``), and the benchmark's own host annotations, whose names
start with ``ANNOTATION_PREFIX``.  The rest of the module is pure
functions over such event lists, so a small recorded list checks them in
the tests.

An event is ``{"kind": "op"|"module"|"host", "plane": str, "name": str,
"t0": ns, "dur": ns, "long": str, "target": str}``.  On a TPU an
operation's event name is its whole HLO instruction; ``name`` keeps the
instruction's name (``rdp_matmul_cols.40``), ``long`` the start of its
text, and ``target`` the custom-call target named there ("" for other
operations).  Operations nest (a ``while`` holds its body's), so times per
operation are self times: an event's duration less its children's.
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
from typing import Iterable

ANNOTATION_PREFIX = "bench."
WINDOW = "bench.traced"            # host annotation spanning the traced window
DEVICE_LINES = {"XLA Ops": "op", "XLA Modules": "module"}
LONG_CHARS = 300
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def extract(trace_dir: str) -> list[dict]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            kind = DEVICE_LINES.get(line.name) if device else "host"
            if kind is None:
                continue
            for ev in line.events:
                if kind == "host" and not ev.name.startswith(
                        ANNOTATION_PREFIX):
                    continue
                stats = dict(ev.stats) if kind == "op" else {}
                long = str(stats.get("long_name", "") or ev.name)
                target = _TARGET.search(long)
                name = ev.name.split(" = ")[0].lstrip("%") if kind == "op" \
                    else ev.name
                events.append({
                    "kind": kind, "plane": plane.name, "name": name,
                    "t0": float(ev.start_ns), "dur": float(ev.duration_ns),
                    "long": long[:LONG_CHARS],
                    "target": target.group(1) if target else ""})
    return events


def inventory(trace_dir: str) -> dict:
    """Plane → line → event count of the newest trace (to look at one)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return {p.name: {ln.name: sum(1 for _ in ln.events) for ln in p.lines}
            for p in data.planes}


def save(events: list[dict], path: str) -> None:
    """Write events as gzipped JSON (for a later look, or a test)."""
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: str) -> list[dict]:
    """Read events written by ``save``."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

def window(events: list[dict]) -> tuple[float, float]:
    """(t0, t1) in ns of the traced window, from its host annotation."""
    spans = [e for e in events if e["kind"] == "host" and e["name"] == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w = max(spans, key=lambda e: e["dur"])
    return w["t0"], w["t0"] + w["dur"]


def device_planes(events: list[dict]) -> list[str]:
    """Device planes that ran at least one operation."""
    return sorted({e["plane"] for e in events if e["kind"] == "op"})


def _clip(events: Iterable[dict], t0: float, t1: float) -> list:
    out = []
    for e in events:
        a, b = max(e["t0"], t0), min(e["t0"] + e["dur"], t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: list) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: list[dict], plane: str, t0: float, t1: float) -> float:
    """Nanoseconds of [t0, t1] in which an operation ran on ``plane``."""
    ops = (e for e in events if e["kind"] == "op" and e["plane"] == plane)
    return sum(b - a for a, b in union(_clip(ops, t0, t1)))


def busy_s(events: list[dict]) -> tuple[float, float]:
    """(busy seconds averaged over the device planes, window seconds)."""
    t0, t1 = window(events)
    planes = device_planes(events)
    if not planes:
        raise ValueError("no operation ran on a device in the traced window")
    busy = sum(busy_ns(events, p, t0, t1) for p in planes) / len(planes)
    return busy / 1e9, (t1 - t0) / 1e9


def gaps(events: list[dict], plane: str) -> list:
    """Idle (start, end) intervals of ``plane`` inside the traced window."""
    t0, t1 = window(events)
    ops = (e for e in events if e["kind"] == "op" and e["plane"] == plane)
    out, cur = [], t0
    for a, b in union(_clip(ops, t0, t1)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def self_times(events: list[dict], plane: str) -> list:
    """(event, self ns) of the operations on ``plane``: each duration less
    the time of the operations nested inside it."""
    ops = sorted((e for e in events if e["kind"] == "op"
                  and e["plane"] == plane), key=lambda e: (e["t0"], -e["dur"]))
    out, stack = [], []                     # stack of [end, index]
    for e in ops:
        t0, t1 = e["t0"], e["t0"] + e["dur"]
        while stack and stack[-1][0] <= t0:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(t1, stack[-1][0]) - t0
        out.append([e, e["dur"]])
        stack.append([t1, len(out) - 1])
    return out


def op_seconds(events: list[dict], match=None) -> dict:
    """Device self seconds per operation name inside the traced window,
    averaged over the device planes; ``match(event)`` keeps a subset."""
    t0, t1 = window(events)
    planes = device_planes(events)
    tot = collections.Counter()
    for p in planes:
        for e, own in self_times(events, p):
            if match is not None and not match(e):
                continue
            inside = min(e["t0"] + e["dur"], t1) - max(e["t0"], t0)
            if inside > 0 and e["dur"] > 0:
                tot[e["name"]] += own * inside / e["dur"] / 1e9
    return {k: v / max(len(planes), 1) for k, v in tot.items()}


def _annotations(events: list[dict]) -> tuple[list, list, float]:
    """The benchmark's host annotations (not the window's) sorted by start,
    their starts, and the longest duration among them."""
    hosts = sorted((e for e in events
                    if e["kind"] == "host" and e["name"] != WINDOW),
                   key=lambda e: e["t0"])
    return (hosts, [e["t0"] for e in hosts],
            max((e["dur"] for e in hosts), default=0.0))


def host_at(events: list[dict], a: float, b: float, _index=None) -> str:
    """The innermost benchmark annotation that overlaps (a, b) the most."""
    hosts, starts, longest = _index or _annotations(events)
    best, best_key = "idle", (0.0, 0.0)
    # only annotations that start after a - longest can reach a
    for e in hosts[bisect.bisect_left(starts, a - longest):
                   bisect.bisect_left(starts, b)]:
        lo, hi = max(a, e["t0"]), min(b, e["t0"] + e["dur"])
        if hi > lo:
            key = (hi - lo, -e["dur"])       # more overlap, then shorter
            if key > best_key:
                best, best_key = e["name"], key
    return best


def idle_by_host(events: list[dict]) -> dict:
    """Idle device seconds in the traced window, named by what the host was
    doing (the benchmark annotation that covered each gap), averaged over
    the device planes."""
    planes = device_planes(events)
    index = _annotations(events)
    tot = collections.Counter()
    for p in planes:
        for a, b in gaps(events, p):
            tot[host_at(events, a, b, index)] += (b - a) / 1e9
    return {k: v / max(len(planes), 1) for k, v in tot.items()}


def module_runs(events: list[dict], plane: str, name_has: str) -> list:
    """(start, end) of each run of the executables whose name contains
    ``name_has`` on ``plane``, in time order, inside the traced window."""
    t0, t1 = window(events)
    runs = [(e["t0"], e["t0"] + e["dur"]) for e in events
            if e["kind"] == "module" and e["plane"] == plane
            and name_has in e["name"] and t0 <= e["t0"] <= t1]
    return sorted(runs)


def top(d: dict, n: int = 10) -> list:
    """The ``n`` largest entries of a name → seconds map, as pairs."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(events: list[dict]) -> dict:
    """The ``breakdown`` of a result line: the device operations that took
    the most time, and the idle gaps by what the host was doing."""
    return {"device_ops": top(op_seconds(events)),
            "idle_gaps": top(idle_by_host(events))}
