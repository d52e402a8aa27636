"""Reduce a profiler trace to device busy and idle time, op times and
calls by name, exposed collective time, idle gaps attributed to the host
annotation that covers them, and, given the compiled text of the step that
ran, device time by the program's phases and named scopes.

A trace here is a plain dict, so that a test can build one by hand::

    {"devices": {"0": [[name, start_ns, dur_ns], ...], ...},
     "async": {"0": [[name, start_ns, dur_ns], ...], ...},
     "modules": {"0": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds the operations of each chip (the "XLA Ops" line of each
TPU plane; an op such as a ``while`` loop encloses the ops of its body,
and the reduction charges each op its self time).  ``async`` holds the
collectives in flight on the "Async XLA Ops" line.  ``modules`` holds the
runs of each compiled program (the "XLA Modules" line: ``jit_step(<id>)``
spans the ops of one run of ``HloModule jit_step``); a trace with no
``modules`` counts every op as the step's.  ``host`` holds the host
annotations whose names start with one of ``HOST_PREFIXES``: the
harness's own and the program's host spans.  The window is the span of
the ``bench.window`` annotation, or of all device operations where there
is none.  An op's name is the HLO instruction (``%fusion.12 = bf16[...]
fusion(...)``); ``short_name`` keeps its left side and the start of its
type, ``instruction`` its left side alone.

The join to the step's compiled text goes by instruction name, inside the
runs of the step's module.  An op's phase is the program's own
(``repro.core.scopes.phases``: forward, backward, recompute, optimizer,
exchange, unscoped); its scopes are the names on the path of its
``op_name`` (a fusion takes its fused root's), with autodiff's
``jvp(...)`` and ``transpose(...)`` taken off, so a scope counts its
forward and its backward.  Ops of other modules go to ``OTHER_MODULE``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

HOST_PREFIXES = ("bench.", "engine.", "data.")
WINDOW = "bench.window"
OTHER_MODULE = "(other module)"
UNSCOPED = "unscoped"
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
    devices, asyncs, modules, host = {}, {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = plane.name[len("/device:TPU:"):]
            ops, coll, mods = [], [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods.extend([e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events)
                elif line.name == "XLA Ops":
                    ops.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                               for e in line.events)
                elif line.name == "Async XLA Ops":
                    coll.extend([e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events
                                if is_collective(e.name))
            devices[dev], asyncs[dev], modules[dev] = ops, coll, mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"devices": devices, "async": asyncs, "modules": modules,
            "host": host}


def short_name(name: str, width: int = 72) -> str:
    lhs, _, rhs = name.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs}"[:width].rstrip()


def instruction(name: str) -> str:
    """The instruction's name of an op: ``fusion.12``."""
    return name.partition(" = ")[0].strip().lstrip("%")


_INSTR = re.compile(r"^\s*(ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)}]+)")
_OPERAND = re.compile(r"%([^\s,()]+)")
_AUTODIFF = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def op_names(hlo_text: str) -> tuple:
    """(module name, {instruction: op_name}) of a compiled module's text.
    A fusion takes the ``op_name`` of its fused computation's root, else of
    the nearest instruction the root reads that has one, else its own."""
    module, comps, comp = None, {}, None
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        if line.rstrip().endswith("{") and " = " not in line:
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            comps[comp] = {}
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        rest = m.group(3)
        op = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        comps[comp][m.group(2)] = (
            op.group(1) if op else "", bool(m.group(1)),
            calls.group(1) if calls and " fusion(" in rest else None,
            _OPERAND.findall(_OP_NAME.sub("", rest)))

    def fused_root(body):
        instrs = comps.get(body, {})
        todo = [n for n, r in instrs.items() if r[1]]
        seen = set()
        while todo:
            n = todo.pop(0)
            if n in seen or n not in instrs:
                continue
            seen.add(n)
            if instrs[n][0]:
                return instrs[n][0]
            todo.extend(instrs[n][3])
        return ""

    out = {}
    for instrs in comps.values():
        for name, (op, _, body, _) in instrs.items():
            out[name] = (fused_root(body) if body else "") or op
    return module, out


def scope_names(op_name: str) -> set:
    """The names on the path of an ``op_name`` (the last part, the
    primitive, left out), each with ``jvp(...)`` and ``transpose(...)``
    taken off; an ``op_name`` of several paths joined by ``;`` gives the
    names of them all."""
    names = set()
    for path in op_name.split(";"):
        for part in path.split("/")[:-1]:
            m = _AUTODIFF.match(part)
            while m:
                part = m.group(1)
                m = _AUTODIFF.match(part)
            if part:
                names.add(part)
    return names


class _Join:
    """Phase and scope names of a device op of the step, from the step's
    compiled text."""

    def __init__(self, hlo_text: str):
        from repro.core.scopes import phases

        self.module, ops = op_names(hlo_text)
        self.phases = phases(hlo_text)
        self.scopes = {k: scope_names(v) for k, v in ops.items()}

    def __call__(self, name: str, module) -> tuple:
        if module is not None and module != self.module:
            return OTHER_MODULE, ()
        ins = instruction(name)
        return self.phases.get(ins, UNSCOPED), self.scopes.get(ins, ())


def _module_at(runs, t):
    """The name of the module whose run spans time ``t`` (``runs``:
    sorted ``(start, end, name)``), ``None`` where there are no runs, and
    ``OTHER_MODULE`` where none spans it."""
    if not runs:
        return None
    i = bisect.bisect_right(runs, (t, float("inf"), "")) - 1
    if i >= 0 and runs[i][0] <= t < runs[i][1]:
        return runs[i][2].partition("(")[0]
    return OTHER_MODULE


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


def reduce_trace(trace: dict, top: int = 10, step_text: str = None) -> dict:
    """Seconds (and calls), averaged over the chips in the trace:

    ``window_s``; ``busy_s`` (union of op intervals in the window);
    ``idle_share`` (1 − busy/window); ``collective_s`` (union of
    collective ops, in flight or not) and ``exposed_collective_s`` (the
    part of it during which no other op runs on that chip); ``op_s``
    (self seconds by op name) and ``op_n`` (calls by op name);
    ``device_ops`` and ``idle_gaps`` (the ``top`` largest, as
    ``[name, seconds]``): idle time is attributed to the innermost host
    annotation, other than the window, that covers the gap's midpoint, or
    to ``"(no annotation)"``.  With the step's compiled text,
    ``phase_s`` (self seconds by phase; they sum to those of ``op_s``) and
    ``scope_s`` (self seconds of the ops that have a scope on their path,
    by scope), else both empty.  An op counts where it overlaps the
    window, by the part of it that does.
    """
    lo, hi = window_of(trace)
    window = hi - lo
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no device")
    n = len(devs)
    notes = sorted(((s, s + d, name) for name, s, d in trace["host"]
                    if name != WINDOW), key=lambda x: x[1] - x[0])
    join = _Join(step_text) if step_text else None
    busy = coll = exposed = 0.0
    op_s, op_n = defaultdict(float), defaultdict(float)
    phase_s, scope_s = defaultdict(float), defaultdict(float)
    gaps = defaultdict(float)
    for dev, evs in devs.items():
        iv = _clip([(s, s + d) for _, s, d in evs], lo, hi)
        merged = _union(iv)
        busy += _length(merged)
        runs = sorted((s, s + d, name) for name, s, d in
                      trace.get("modules", {}).get(dev, []))
        for name, s, e, self_ns in _self_times(evs):
            c = _clip([(s, e)], lo, hi)
            if c and e > s:
                t = self_ns * (c[0][1] - c[0][0]) / (e - s) / n
                op_s[name] += t
                op_n[name] += 1 / n
                if join:
                    phase, scopes = join(name, _module_at(runs, s))
                    phase_s[phase] += t
                    for scope in scopes:
                        scope_s[scope] += t
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
        "op_n": dict(op_n),
        "phase_s": {k: v * ns for k, v in phase_s.items()},
        "scope_s": {k: v * ns for k, v in scope_s.items()},
        "device_ops": [[short_name(k), v * ns] for k, v in
                       sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def op_seconds(reduced: dict, match) -> float:
    """Seconds per chip of the ops whose name satisfies ``match``."""
    return sum(v for k, v in reduced["op_s"].items() if match(k))
