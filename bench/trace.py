"""From a profiler trace (`.xplane.pb`) to device busy time, kernel time
and the breakdown.

`load` flattens the trace into plain tuples: device operations per TPU
(the "XLA Ops" line of each ``/device:TPU:<n>`` plane) and the host spans
of every host thread. `reduce` does the arithmetic on those tuples, so the
tests can check it on events of their own:

* busy: the union of the device-operation intervals inside the window,
  averaged over the devices that ran any;
* op time (`op_time`): the summed device durations, inside the window, of
  the operations whose HLO text matches a pattern;
* breakdown: the ten device operations that took most time, and the ten
  longest idle gaps of the first device, each named by the innermost
  ``bench.*`` span that was open on the host at the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Op = Tuple[str, int, int]            # name, start ns, end ns
Span = Tuple[str, int, int]

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def latest(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Tuple[Dict[str, List[Op]], List[Span]]:
    """(device name -> operations, host spans) of one trace file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events if e.name.startswith("bench."))
    return devices, spans


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ops: Sequence[Op], lo: int, hi: int) -> List[Op]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops
            if e > lo and s < hi]


def window_of(spans: Sequence[Span]) -> Tuple[int, int]:
    w = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not w:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return min(s for s, _ in w), max(e for _, e in w)


_OP = re.compile(r"^(%?[\w.-]+) = (\S+) ([a-z][\w-]*)\(")


def short(name: str) -> str:
    """An XLA op's event name is its whole HLO instruction; keep its name,
    result shape and opcode (``%gnn_mp.14 f32[512,32,300]{..} custom-call``)."""
    m = _OP.match(name)
    return " ".join(m.groups()) if m else name[:120]


def _span_at(spans: Sequence[Span], t: int) -> str:
    best: Optional[Span] = None
    for sp in spans:
        if sp[0] != WINDOW_SPAN and sp[1] <= t < sp[2]:
            if best is None or sp[2] - sp[1] < best[2] - best[1]:
                best = sp
    return best[0] if best else "no bench span"


def op_time(devices: Dict[str, List[Op]], spans: Sequence[Span],
            pattern: "re.Pattern") -> float:
    """Device seconds, inside the window, of the ops `pattern` matches."""
    lo, hi = window_of(spans)
    return sum((e - s) * 1e-9 for ops in devices.values()
               for n, s, e in _clip(ops, lo, hi) if pattern.match(n))


def reduce(devices: Dict[str, List[Op]], spans: Sequence[Span],
           top: int = 10) -> Dict:
    """Busy seconds and the breakdown of the traced window."""
    lo, hi = window_of(spans)
    window_s = (hi - lo) * 1e-9
    busy, per_op, first = [], {}, None
    for name in sorted(devices):
        ops = _clip(devices[name], lo, hi)
        if not ops:
            continue
        merged = union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if first is None:
            first = merged
        for n, s, e in ops:
            per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9
    gaps = []
    if first is not None:
        edges = [lo] + [t for iv in first for t in iv] + [hi]
        gaps = sorted(((s, e) for s, e in zip(edges[0::2], edges[1::2])
                       if e > s), key=lambda g: g[0] - g[1])[:top]
    gaps = [(_span_at(spans, (s + e) // 2), (e - s) * 1e-9) for s, e in gaps]
    ops_top = [(short(n), s) for n, s in
               sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": window_s,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "devices": len(busy),
            "breakdown": {"device_ops": [[n, s] for n, s in ops_top],
                          "idle_gaps": [[n, s] for n, s in gaps]}}
