"""The program's own spans and named scopes in a device trace.

``trace_reduce`` keeps the benchmark's annotations and the device's
operations.  This module adds what the program itself writes into the
same trace, on the same clock:

* its host spans (``SpanTracer.span`` in ``obs/trace.py`` opens a profiler
  annotation of the span's name): ``DistributedTrainer.run`` names every
  phase of a step ``train.<phase>`` (``run``, ``sample``, ``data``, ``lr``,
  ``dispatch``, ``sync``, ``record``).  They become events of kind
  ``span``;
* the named scope of each device operation: the model and the train step
  wrap their parts in ``jax.named_scope`` (``SCOPES``), and the scope's
  name lands in the ``op_name`` of every operation made from it, forward
  and backward.  A TPU trace's operation events do not carry it: the
  ``op_name`` is in the HLO of each executable, which the profiler keeps
  in the trace's ``/host:metadata`` plane (``hlo_op_names``).  Each ``op``
  event gains ``scope``: the first of ``SCOPES`` on the ``op_name`` of
  its instruction, in the executable that ran around it, or "".

``with_program`` adds both to the events ``trace_reduce.extract`` gave,
so every function of ``trace_reduce`` reads the result too;
``scope_seconds`` and ``idle_by_span`` are pure functions of such a list.
A trace of a program that writes neither (an older commit) gives no span
and no scope, and the readers built on them return None.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

import run as R
import trace_reduce as TR

SPAN_PREFIX = "train."
SCOPES = ("embed", "attn", "ffn", "head", "optimizer")
OUTSIDE = "outside"         # idle time no program span covers: the harness
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def scope_of(op_name: str) -> str:
    """The first of ``SCOPES`` on an ``op_name`` path, or "".  A path
    component names a scope bare (``attn``) or inside transformations
    (``transpose(jvp(attn))``)."""
    for part in op_name.split("/"):
        name = part.rstrip(")").rpartition("(")[2]
        if name in SCOPES:
            return name
    return ""


# --------------------------------------------------------------------------
# the HLO in the trace: protobuf read by field number, no generated code
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    byte views for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _instruction_op_names(hlo_proto) -> dict:
    """Instruction name → ``op_name`` over every computation of an
    ``HloProto`` (hlo_module 1 → computations 3 → instructions 2 → name 1,
    metadata 7 → op_name 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, instr in _fields(comp):
                if h != 2:
                    continue
                name = op = ""
                for k, v in _fields(instr):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op = next((_text(w) for j, w in _fields(v)
                                   if j == 2), "")
                out[name] = op
    return out


def hlo_op_names(path, modules=None) -> dict:
    """Executable name (as the trace's ``XLA Modules`` line names it) →
    {instruction name → ``op_name``}, from the HLO protos in the
    ``/host:metadata`` plane of an ``.xplane.pb`` (XSpace planes 1; XPlane
    name 2, event_metadata 4, stat_metadata 5; XEventMetadata name 2,
    stats 5; XStat metadata_id 1, bytes_value 6); only the executables
    named in ``modules``, where given."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for f, plane in _fields(space):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for g, v in fields if g == 2), "") \
                != METADATA_PLANE:
            continue
        stat_names = {}
        for g, v in fields:
            if g == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1)] = _text(meta.get(2, b""))
        out = {}
        for g, v in fields:
            if g != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            name = next((_text(w) for h, w in meta if h == 2), "")
            if modules is not None and name not in modules:
                continue
            for h, w in meta:
                stat = dict(_fields(w)) if h == 5 else {}
                if stat_names.get(stat.get(1)) == HLO_STAT and 6 in stat:
                    out[name] = _instruction_op_names(stat[6])
        return out
    return {}


def with_program(events: list[dict], path) -> list[dict]:
    """``events`` (from ``trace_reduce.extract`` of the ``.xplane.pb`` at
    ``path``) with the program's spans added and each operation's scope
    set; the given events are copied, not changed."""
    from jax.profiler import ProfileData
    out = [dict(e, scope="") for e in events]
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [{"kind": "span", "plane": plane.name, "name": ev.name,
                     "t0": float(ev.start_ns), "dur": float(ev.duration_ns),
                     "long": ev.name, "target": "", "scope": ""}
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX)]
    modules = {e["name"] for e in out if e["kind"] == "module"}
    _put_scopes(out, hlo_op_names(path, modules))
    return out


def _put_scopes(events: list, op_names: dict) -> None:
    """Set each op event's scope from the ``op_name`` of its instruction
    in the executable that ran around it on the same plane."""
    runs = collections.defaultdict(list)
    for e in events:
        if e["kind"] == "module":
            runs[e["plane"]].append((e["t0"], e["t0"] + e["dur"], e["name"]))
    for r in runs.values():
        r.sort()
    starts = {p: [a for a, _b, _n in r] for p, r in runs.items()}
    for e in events:
        if e["kind"] != "op" or e["plane"] not in runs:
            continue
        i = bisect.bisect_right(starts[e["plane"]], e["t0"]) - 1
        if i < 0:
            continue
        _a, end, module = runs[e["plane"]][i]
        if e["t0"] < end:
            e["scope"] = scope_of(op_names.get(module, {}).get(e["name"], ""))


_LAST: dict = {}          # the one reading the readers of a run share


def events(ctx: dict) -> list:
    """The traced window's events with spans and scopes, for a reader.

    ``ctx["events"]`` where they carry them already (a recorded slice in
    the tests); else those events with what ``with_program`` reads from
    the trace ``run.py`` has just reduced, while its ``TRACE_DIR`` still
    holds it (read once for all the readers of a run); else
    ``ctx["events"]`` as they are."""
    given = ctx["events"]
    if any(e["kind"] == "span" or "scope" in e for e in given):
        return given
    paths = glob.glob(os.path.join(str(R.TRACE_DIR), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return given
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if _LAST.get("key") != key or _LAST.get("given") is not given:
        _LAST.update(key=key, given=given, out=with_program(given, path))
    return _LAST["out"]


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

def scope_seconds(events: list[dict], scope: str) -> float:
    """Device self seconds of the operations in ``scope`` inside the
    traced window, averaged over the device planes."""
    return sum(TR.op_seconds(
        events, lambda e: e.get("scope") == scope).values())


def innermost(spans: list[dict]) -> list:
    """Disjoint (start, end, name) pieces of time, each named by the
    innermost (shortest) span that covers it; time no span covers is left
    out."""
    spans = sorted(spans, key=lambda e: e["t0"])
    cuts = sorted({t for e in spans for t in (e["t0"], e["t0"] + e["dur"])})
    out, active, nxt = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(spans) and spans[nxt]["t0"] <= a:
            active.append(spans[nxt])
            nxt += 1
        active = [e for e in active if e["t0"] + e["dur"] > a]
        if active:
            out.append((a, b, min(active, key=lambda e: e["dur"])["name"]))
    return out


def idle_by_span(events: list[dict]) -> dict:
    """Idle device seconds in the traced window, each nanosecond put down
    to the innermost program span covering it, or to ``OUTSIDE`` where no
    span does; averaged over the device planes."""
    planes = TR.device_planes(events)
    pieces = innermost([e for e in events if e["kind"] == "span"])
    starts = [a for a, _b, _n in pieces]
    tot = collections.Counter()
    for p in planes:
        for a, b in TR.gaps(events, p):
            covered = 0.0
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            for s0, s1, name in pieces[i:bisect.bisect_left(starts, b)]:
                overlap = min(b, s1) - max(a, s0)
                if overlap > 0:
                    tot[name] += overlap
                    covered += overlap
            tot[OUTSIDE] += (b - a) - covered
    return {k: v / 1e9 / max(len(planes), 1) for k, v in tot.items()}


def traced_steps(ctx: dict) -> int:
    """Steps of the window that ran while the profiler traced."""
    return sum(1 for *_rest, traced in ctx["counters"]["steps"] if traced)


def scope_ms(ctx: dict, scope: str):
    """Device ms of ``scope`` per traced step; None where no operation of
    the trace carries it."""
    n = traced_steps(ctx)
    s = scope_seconds(events(ctx), scope)
    return 1e3 * s / n if n and s > 0 else None


def idle_ms(ctx: dict, names: tuple):
    """Idle device ms per traced step under the spans ``names``; None where
    the trace holds none of them."""
    ev = events(ctx)
    n = traced_steps(ctx)
    if not n or not any(e["kind"] == "span" and e["name"] in names
                        for e in ev):
        return None
    idle = idle_by_span(ev)
    return 1e3 * sum(idle.get(k, 0.0) for k in names) / n
