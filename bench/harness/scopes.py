"""Where a window's device time and idle time go, by the program's names.

The program names its phases.  Every operation of the fused super-step
carries in its XLA metadata the ``jax.named_scope`` of the phase that
emitted it (``asfl.control``, ``asfl.batch``, ``asfl.grad``, ``asfl.wire``,
``asfl.server_update``, ``asfl.client_update``, ``asfl.merge``;
``asfl.eval`` in the evaluation program), and ``ScenarioEngine`` wraps its
host work in ``jax.profiler.TraceAnnotation`` spans (``engine.window``
around ``engine.stage``, ``engine.dispatch``, ``engine.pull``,
``engine.global_model``, ``engine.metrics``, ``engine.evaluate``).  Both
land in the profiler's one ``.xplane.pb``, on one clock.

``annotate`` adds to a normalized trace (``harness/trace.py``) each device
operation's scope and program.  The reducers read only the normalized
form:

* ``device_ns_per_window``: every op-nanosecond of the fullest device in
  the traced window on exactly one scope (loops and calls left out, as
  ``trace.top_ops`` leaves them out), per window;
* ``idle_ns_per_window``: every idle nanosecond of the fullest device on
  the innermost ``engine.*`` span over it (or on none), per window.  The
  values sum to ``trace.idle_per_window_ns``.  It reads the host spans
  that ``load`` keeps (20 us or longer): idle under a shorter span falls
  to the span around it, in the same group for every span the engine
  has.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional

from harness import trace as T

SCOPE = re.compile(r"asfl\.[a-z_]+")
ENGINE = "engine."
NO_SCOPE = "(no scope)"
OUTSIDE = "(no engine span)"
# the device metrics' groups of scopes; every other scope, and ops in
# none, fall to other_device_ms
DEVICE_GROUPS = {"grad_ms": ("asfl.grad", "asfl.wire"),
                 "server_update_ms": ("asfl.server_update",),
                 "client_update_ms": ("asfl.client_update",),
                 "batch_ms": ("asfl.batch",),
                 "merge_ms": ("asfl.merge",)}
# the idle metrics' spans; every other span, and no span, fall to
# other_idle_ms
IDLE_GROUPS = {"pull_idle_ms": "engine.pull",
               "metrics_idle_ms": "engine.metrics",
               "eval_idle_ms": "engine.evaluate"}


def scope_of(text: str) -> str:
    """The innermost ``asfl.*`` component of an op's name path."""
    found = SCOPE.findall(text or "")
    return found[-1] if found else ""


def _varint(buf, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of the protobuf message in ``buf[i:end]``;
    a length-delimited value comes as its (start, end) in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _op_names(buf, span, table: Dict[str, str]) -> None:
    """Each instruction of a serialized ``HloProto`` into ``table`` as
    {name: scope}: HloProto.hlo_module (1) -> computations (3) ->
    instructions (2) -> name (1), metadata (7) -> op_name (2)."""
    for f, module in _fields(buf, *span):
        if f != 1:
            continue
        for mf, comp in _fields(buf, *module):
            if mf != 3:
                continue
            for cf, ins in _fields(buf, *comp):
                if cf != 2:
                    continue
                name, scope = "", ""
                for f2, v2 in _fields(buf, *ins):
                    if f2 == 1:
                        name = _text(buf, v2)
                    elif f2 == 7:
                        for f3, v3 in _fields(buf, *v2):
                            if f3 == 2:
                                scope = scope_of(_text(buf, v3))
                table[name] = scope


def program_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: scope}} for every program in the profile:
    the profiler keeps each optimized program's ``HloProto``, whose
    instructions carry their ``op_name``, in the ``/host:metadata`` plane
    (an event metadata per program, named like the ``XLA Modules``
    events, with the proto as a bytes stat).  The profiler's Python
    reader does not expose event metadata, so this reads the XSpace
    itself: planes (1) -> name (2), event metadata (4: map entry value
    2 -> name 2, stats 5 -> bytes 6)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        fields = list(_fields(buf, *plane))
        if not any(f == 2 and _text(buf, v) == "/host:metadata"
                   for f, v in fields):
            continue
        for f, entry in fields:
            if f != 4:
                continue
            meta = list(_fields(buf, *dict(_fields(buf, *entry))[2]))
            table = out.setdefault(
                next((_text(buf, v) for mf, v in meta if mf == 2), ""), {})
            for mf, stat in meta:
                if mf == 5:
                    for sf, sv in _fields(buf, *stat):
                        if sf == 6:
                            _op_names(buf, sv, table)
    return out


def annotate(trace: Dict, path: str) -> Dict:
    """``trace`` (``harness.trace.load(path)``) with one more key,
    ``op_meta``: per device a ``[scope, program]`` for each op of
    ``trace["devices"]``, in the same order.  The program is the ``XLA
    Modules`` event the op runs in, and the scope that of the op's
    instruction in that program (scope "" where it carries none).  The
    keys that ``load`` wrote are left as they are."""
    from jax.profiler import ProfileData
    programs = program_scopes(path)
    meta: Dict[str, List] = {}
    for plane in ProfileData.from_file(path).planes:
        m = T._DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = list(plane.lines)
        ops = [l for l in lines if l.name == "XLA Ops"] or \
            [l for l in lines if l.name not in T._NOT_OPS]
        modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                          e.name) for l in lines if l.name == "XLA Modules"
                         for e in l.events)
        starts = [s for s, _, _ in modules]
        rows = []
        for line in ops:
            for e in line.events:
                s, name = int(e.start_ns), T.op_name(e.name)
                i = bisect.bisect_right(starts, s) - 1
                program = modules[i][2] if i >= 0 and s < modules[i][1] \
                    else ""
                scope = programs.get(program, {}).get(name.split(" ")[0], "")
                rows.append([s, int(e.duration_ns), name, scope,
                             program.split("(")[0]])
        rows.sort()
        meta[f"{m.group(1)}:{m.group(2)}"] = [r[3:] for r in rows]
    return dict(trace, op_meta=meta)


def _labels(spans: List[List], lo: int, hi: int):
    """[(start, end, name)] cutting [lo, hi) into pieces, each named by
    the innermost span over it (the latest to start; spans nest on the
    calling thread), or ``OUTSIDE``."""
    cuts = sorted({lo, hi} | {min(max(p, lo), hi) for s, d, _ in spans
                              for p in (s, s + d)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(s, -d, n) for s, d, n in spans if s <= a and b <= s + d]
        out.append((a, b, max(over)[2] if over else OUTSIDE))
    return out


def idle_ns_per_window(trace: Dict) -> Optional[Dict[str, float]]:
    """Idle ns of the fullest device per window, by the innermost
    ``engine.*`` span over each idle instant.  None without a device or
    without any engine span (a program that has none)."""
    spans = [h for h in trace["host"] if h[2].startswith(ENGINE)]
    dev, b, ws = T.fullest(trace), T.bounds(trace), T.windows(trace)
    if dev is None or not spans:
        return None
    labels = _labels(spans, *b)
    busy = T.busy_intervals(trace, dev, *b)
    gaps, cur = [], b[0]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if b[1] > cur:
        gaps.append((cur, b[1]))
    out: Dict[str, float] = {}
    i = 0
    for s, e in gaps:
        while labels[i][1] <= s:
            i += 1
        j = i
        while j < len(labels) and labels[j][0] < e:
            a, c, name = labels[j]
            ov = min(c, e) - max(a, s)
            if ov > 0:
                out[name] = out.get(name, 0) + ov
            j += 1
    return {k: v / len(ws) for k, v in out.items()}


def idle_ms(trace: Dict, metric: str) -> Optional[float]:
    """One idle metric (a key of ``IDLE_GROUPS``, or ``other_idle_ms``)."""
    ns = idle_ns_per_window(trace)
    if ns is None:
        return None
    if metric in IDLE_GROUPS:
        return ns.get(IDLE_GROUPS[metric], 0.0) / 1e6
    named = set(IDLE_GROUPS.values())
    return sum(v for k, v in ns.items() if k not in named) / 1e6


def device_ns_per_window(trace: Dict) -> Optional[Dict[str, Dict]]:
    """Op ns and op count of the fullest device per window, by scope
    (``NO_SCOPE`` for ops that carry none), with each scope's programs.
    None where the trace holds no op scopes (``annotate`` not run)."""
    dev, b, ws = T.fullest(trace), T.bounds(trace), T.windows(trace)
    if dev is None or "op_meta" not in trace:
        return None
    out: Dict[str, Dict] = {}
    for (s, d, name), (scope, module) in zip(trace["devices"][dev],
                                             trace["op_meta"][dev]):
        if b[0] <= s < b[1] and not T._CONTAINER.match(name):
            row = out.setdefault(scope or NO_SCOPE,
                                 {"ns": 0, "ops": 0, "programs": {}})
            row["ns"] += d
            row["ops"] += 1
            row["programs"][module] = row["programs"].get(module, 0) + d
    for row in out.values():
        row["ns"] /= len(ws)
        row["ops"] /= len(ws)
        row["programs"] = {k: v / len(ws)
                           for k, v in row["programs"].items()}
    return out


def device_ms(trace: Dict) -> Optional[Dict[str, float]]:
    """The six device metrics: ``DEVICE_GROUPS`` and ``other_device_ms``,
    which sum to the op time per window."""
    rows = device_ns_per_window(trace)
    if rows is None:
        return None
    out = {m: 0.0 for m in DEVICE_GROUPS}
    out["other_device_ms"] = 0.0
    for scope, row in rows.items():
        m = next((m for m, g in DEVICE_GROUPS.items() if scope in g),
                 "other_device_ms")
        out[m] += row["ns"] / 1e6
    return out
