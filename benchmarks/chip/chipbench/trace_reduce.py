"""Reduce a profiler trace to device busy and idle time, op times by name,
exposed collective time, and idle gaps attributed to the host annotation
that covers them.

A trace here is a plain dict, so that a test can build one by hand::

    {"devices": {"0": [[name, start_ns, dur_ns], ...], ...},
     "async": {"0": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds the operations of each chip (the "XLA Ops" line of each
TPU plane; an op such as a ``while`` loop encloses the ops of its body,
and the reduction charges each op its self time).  ``async`` holds the
collectives in flight on the "Async XLA Ops" line.  ``host`` holds the
host annotations whose names start with one of ``HOST_PREFIXES``.  The
window is the span of the ``bench.window`` annotation, or of all device
operations where there is none.  An op's name is the HLO instruction
(``%fusion.12 = bf16[...] fusion(...)``); ``short_name`` keeps its left
side and the start of its type.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

HOST_PREFIXES = ("bench.", "engine.")
WINDOW = "bench.window"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


def load_xplane(path: str) -> dict:
    """The trace dict from an ``.xplane.pb`` file (or the newest one under
    a profiler output directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(files, key=os.path.getmtime)
    data = ProfileData.from_file(path)
    devices, asyncs, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = plane.name[len("/device:TPU:"):]
            ops, coll = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                               for e in line.events)
                elif line.name == "Async XLA Ops":
                    coll.extend([e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events
                                if is_collective(e.name))
            devices[dev], asyncs[dev] = ops, coll
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"devices": devices, "async": asyncs, "host": host}


def short_name(name: str, width: int = 72) -> str:
    lhs, _, rhs = name.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs}"[:width].rstrip()


def _self_times(evs):
    """[(name, start, end, self)] of ops that may enclose others."""
    out = []
    stack = []  # indices into out of the open enclosing ops
    for name, s, d in sorted(evs, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][2]:  # enclosed: a child
            out[stack[-1]][3] -= d
        out.append([name, s, e, d])
        stack.append(len(out) - 1)
    return out


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def _subtract(a, b):
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def window_of(trace: dict) -> tuple:
    wins = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    if wins:
        return min(s for s, _ in wins), max(e for _, e in wins)
    ops = [(s, s + d) for evs in trace["devices"].values() for _, s, d in evs]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in ops), max(e for _, e in ops)


def reduce_trace(trace: dict, top: int = 10) -> dict:
    """Seconds, averaged over the chips in the trace:

    ``window_s``; ``busy_s`` (union of op intervals in the window);
    ``idle_share`` (1 − busy/window); ``collective_s`` (union of
    collective ops, in flight or not) and ``exposed_collective_s`` (the
    part of it during which no other op runs on that chip); ``op_s``
    (self seconds by op name);
    ``device_ops`` and ``idle_gaps`` (the ``top`` largest, as
    ``[name, seconds]``): idle time is attributed to the innermost host
    annotation, other than the window, that covers the gap's midpoint, or
    to ``"(no annotation)"``.
    """
    lo, hi = window_of(trace)
    window = hi - lo
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no device")
    n = len(devs)
    notes = sorted(((s, s + d, name) for name, s, d in trace["host"]
                    if name != WINDOW), key=lambda x: x[1] - x[0])
    busy = coll = exposed = 0.0
    op_s = defaultdict(float)
    gaps = defaultdict(float)
    for dev, evs in devs.items():
        iv = _clip([(s, s + d) for _, s, d in evs], lo, hi)
        merged = _union(iv)
        busy += _length(merged)
        for name, s, e, self_ns in _self_times(evs):
            c = _clip([(s, e)], lo, hi)
            if c and e > s:
                op_s[name] += self_ns * (c[0][1] - c[0][0]) / (e - s) / n
        in_flight = trace.get("async", {}).get(dev, [])
        c_iv = _union(_clip([(s, s + d) for name, s, d in evs + in_flight
                             if is_collective(name)], lo, hi))
        other = _union(_clip([(s, s + d) for name, s, d in evs
                              if not is_collective(name)], lo, hi))
        coll += _length(c_iv)
        exposed += _length(_subtract(c_iv, other))
        for s, e in _subtract([[lo, hi]], merged):
            mid = (s + e) / 2
            who = next((nm for a, b, nm in notes if a <= mid < b),
                       "(no annotation)")
            gaps[who] += (e - s) / n
    ns = 1e-9
    busy_s = busy / n * ns
    return {
        "window_s": window * ns,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window * ns) if window > 0 else None,
        "collective_s": coll / n * ns,
        "exposed_collective_s": exposed / n * ns,
        "chips": n,
        "op_s": {k: v * ns for k, v in op_s.items()},
        "device_ops": [[short_name(k), v * ns] for k, v in
                       sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def op_seconds(reduced: dict, match) -> float:
    """Seconds per chip of the ops whose name satisfies ``match``."""
    return sum(v for k, v in reduced["op_s"].items() if match(k))
