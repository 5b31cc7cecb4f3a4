"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read. Needs nothing but JAX (``jax.profiler.ProfileData``).

A TPU's plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation, with start and duration in nanoseconds.
Busy time is the union of those intervals; a kernel's time is the sum of the
durations of the events that carry its name. Host threads are lines of the
plane ``/host:CPU``, on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
#: idle gaps shorter than this are launch latency between two operations,
#: not something the host did
GAP_FLOOR_NS = 50_000


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union_ns(intervals: List[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total covered length of ``(start, end)`` intervals, and the gaps
    between the merged pieces."""
    busy, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def op_key(name: str) -> str:
    """``<instruction> <result shape>`` of an ``XLA Ops`` event, whose name
    is the whole HLO line (``%fusion.12 = f32[8,128]{1,0} fusion(%a, ...)``).
    The instruction's ``.N`` suffix and the layouts are dropped, so that the
    twelve layers' calls of one kernel share a key; the operands are dropped
    because they carry the names of OTHER instructions."""
    own, _, rest = name.partition(" = ")
    base = re.sub(r"\.\d+$", "", own.lstrip("%"))
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    return base + " " + re.sub(r"\{[^}]*\}", "", rest[:end])


def short_name(name: str) -> str:
    """A name the ledger can carry: no spaces, commas or brackets."""
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name)[:64].strip("_")


def _device_planes(data):
    return [p for p in data.planes if p.name.startswith("/device:TPU:")]


def reduce_file(path: str, window_s: Optional[float] = None
                ) -> Optional[Dict[str, Any]]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = _device_planes(data)
    if not planes:
        return None
    busy_ns: List[int] = []
    op_ns: Dict[str, int] = {}
    op_calls: Dict[str, int] = {}
    first_gaps: List[Tuple[int, int]] = []
    span = [None, None]
    for i, plane in enumerate(planes):
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                intervals.append((s, e))
                if i == 0:
                    key = op_key(ev.name)
                    op_ns[key] = op_ns.get(key, 0) + e - s
                    op_calls[key] = op_calls.get(key, 0) + 1
        if not intervals:
            continue
        busy, gaps = union_ns(intervals)
        busy_ns.append(busy)
        lo, hi = min(s for s, _ in intervals), max(e for _, e in intervals)
        span = [lo if span[0] is None else min(span[0], lo),
                hi if span[1] is None else max(span[1], hi)]
        if i == 0:
            first_gaps = gaps
    if not busy_ns:
        return None
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    traced_s = (span[1] - span[0]) / 1e9
    idle = _idle_by_host(data, first_gaps)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": float(window_s) if window_s else traced_s,
        "device_span_s": traced_s,
        "op_seconds": {k: v / 1e9 for k, v in op_ns.items()},
        "op_calls": op_calls,
        "breakdown": {
            "device_ops": [[short_name(k), v / 1e9] for k, v in top],
            "idle_gaps": [[short_name(k), v] for k, v in idle[:10]]},
    }


def _idle_by_host(data, gaps: List[Tuple[int, int]]) -> List[Tuple[str, float]]:
    """Idle seconds of the first device, by the innermost host event that
    covers the middle of each gap."""
    gaps = [(s, e) for s, e in gaps if e - s >= GAP_FLOOR_NS]
    if not gaps:
        return []
    host = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                d = int(ev.duration_ns)
                if d >= GAP_FLOOR_NS // 10:
                    host.append((int(ev.start_ns), int(ev.start_ns) + d,
                                 ev.name))
    out: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) // 2
        best = None
        for hs, he, name in host:
            if hs <= mid <= he and (best is None or he - hs < best[0]):
                best = (he - hs, name)
        name = best[1] if best else "no_host_event"
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return sorted(out.items(), key=lambda kv: -kv[1])


def reduce(trace_dir: str, window_s: Optional[float] = None
           ) -> Optional[Dict[str, Any]]:
    path = find_xplane(trace_dir)
    return reduce_file(path, window_s) if path else None


def kernel_seconds(trace: Dict[str, Any], marker: str) -> Tuple[float, int]:
    """Device seconds and call count of the operations whose own name holds
    ``marker`` (a kernel's ``name=`` at its ``pallas_call``)."""
    hit = [k for k in trace["op_seconds"] if marker in k.split(" ", 1)[0]]
    return (sum(trace["op_seconds"][k] for k in hit),
            sum(trace["op_calls"][k] for k in hit))


def roofline_share(trace: Dict[str, Any],
                   costs: Dict[str, Tuple[float, float]],
                   peaks: Dict[str, float]) -> Optional[float]:
    """Per cent of their roofline that the kernels of ``costs`` (``{marker:
    (flops, bytes) of one call}``) reached: the least time the peaks allow
    for every traced call over the calls' traced time. ``None`` where the
    trace holds none of them."""
    from .kernel_cost import least_seconds
    spent = least = 0.0
    for marker, (flops, moved) in costs.items():
        secs, calls = kernel_seconds(trace, marker)
        spent += secs
        least += calls * least_seconds(flops, moved, peaks)
    return 100.0 * least / spent if spent > 0 else None


def describe(path: str, per_line: int = 12) -> str:
    """Planes, lines and a few event names of a trace, as text: what to look
    at by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            seen = []
            for ev in events:
                if ev.name not in seen:
                    seen.append(ev.name)
                    stats = {k: str(v)[:60] for k, v in list(ev.stats)[:6]}
                    out.append(f"    {ev.name[:150]!r} start={ev.start_ns} "
                               f"dur={ev.duration_ns} {stats}")
                if len(seen) >= per_line:
                    break
    return "\n".join(out)
