"""Reduction of a profiler trace to per-layer numbers.

``load`` turns the profiler's ``.xplane.pb`` into a small normalized
trace: per device, its operations as ``[start_ns, dur_ns, name]``; on the
host, spans as ``[start_ns, dur_ns, name]`` (the benchmark's
``bench.window`` annotations and the host's own spans).  Every reducer
below reads only the normalized form, so a recorded trace in the tests
checks them without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|allreduce|allgather")
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# device lines that aggregate rather than time single operations
_NOT_OPS = ("XLA Modules", "Steps", "Launch Stats", "Framework Ops",
            "Source code", "Host Offload", "Frameworks")
_MIN_HOST_NS = 20_000
# control flow whose span covers the operations of its body
_CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")


def op_name(text: str) -> str:
    """``%fusion.12 = f32[..] fusion(..), kind=kOutput, ..`` ->
    ``fusion.12 kOutput``: the instruction's name, and a fusion's kind."""
    name = text.split(" = ", 1)[0].lstrip("%")
    kind = re.search(r"kind=(k\w+)", text)
    return f"{name} {kind.group(1)}" if kind else name


def latest_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> Dict:
    """Normalized trace of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    host: List = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = list(plane.lines)
            ops = [l for l in lines if l.name == "XLA Ops"] or \
                [l for l in lines if l.name not in _NOT_OPS]
            evs = devices.setdefault(f"{m.group(1)}:{m.group(2)}", [])
            for line in ops:
                for e in line.events:
                    evs.append([int(e.start_ns), int(e.duration_ns),
                                op_name(e.name)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW or e.duration_ns >= _MIN_HOST_NS:
                        host.append([int(e.start_ns), int(e.duration_ns),
                                     e.name])
    for evs in devices.values():
        evs.sort()
    host.sort()
    return {"devices": devices, "host": host}


def save(trace: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def windows(trace: Dict) -> List[Tuple[int, int]]:
    """(start, end) of every ``bench.window`` span, in order."""
    return [(s, s + d) for s, d, n in trace["host"] if n == WINDOW]


def bounds(trace: Dict) -> Optional[Tuple[int, int]]:
    w = windows(trace)
    return (w[0][0], w[-1][1]) if w else None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(trace: Dict, device: str, lo: int, hi: int,
                   pattern: Optional[re.Pattern] = None):
    iv = []
    for s, d, name in trace["devices"].get(device, []):
        if pattern is not None and not pattern.search(name):
            continue
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            iv.append((a, b))
    return _union(iv)


def busy_ns(trace: Dict, device: str, lo: int, hi: int,
            pattern: Optional[re.Pattern] = None) -> int:
    return sum(e - s for s, e in busy_intervals(trace, device, lo, hi,
                                                pattern))


def fullest(trace: Dict) -> Optional[str]:
    """The device busiest over the traced window."""
    b = bounds(trace)
    if b is None or not trace["devices"]:
        return None
    return max(trace["devices"], key=lambda d: busy_ns(trace, d, *b))


def device_times(trace: Dict) -> Optional[Tuple[float, float]]:
    """(busy seconds averaged over the devices, window seconds)."""
    b = bounds(trace)
    if b is None or not trace["devices"]:
        return None
    busy = [busy_ns(trace, d, *b) for d in trace["devices"]]
    return sum(busy) / len(busy) / 1e9, (b[1] - b[0]) / 1e9


def idle_per_window_ns(trace: Dict) -> Optional[float]:
    """Device idle of the fullest device over the traced window, per
    window: what the host's work between and around the fused programs
    costs the device each window."""
    dev, b, ws = fullest(trace), bounds(trace), windows(trace)
    if dev is None:
        return None
    return ((b[1] - b[0]) - busy_ns(trace, dev, *b)) / len(ws)


def top_ops(trace: Dict, k: int = 10) -> List[List]:
    """Device operations that took most time on the fullest device,
    summed by name, in seconds (loops and calls, which span their bodies'
    operations, left out)."""
    dev, b = fullest(trace), bounds(trace)
    if dev is None:
        return []
    tot: Dict[str, int] = {}
    for s, d, name in trace["devices"][dev]:
        if b[0] <= s < b[1] and not _CONTAINER.match(name):
            tot[name] = tot.get(name, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def idle_gaps(trace: Dict, k: int = 10) -> List[List]:
    """The longest idle gaps of the fullest device, each named by the
    shortest host span that covers its middle, in seconds."""
    dev, b = fullest(trace), bounds(trace)
    if dev is None:
        return []
    busy = busy_intervals(trace, dev, *b)
    gaps, cur = [], b[0]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if b[1] > cur:
        gaps.append((cur, b[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) // 2
        cover = [(d, n) for hs, d, n in trace["host"]
                 if hs <= mid < hs + d and n != WINDOW]
        name = min(cover)[1] if cover else "no host span"
        out.append([name, (e - s) / 1e9])
    return out
