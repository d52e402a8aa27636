"""Names of the training step's phases, as the compiled program and the
profiler's trace carry them.

Device scopes (``jax.named_scope``) reach the ``op_name`` metadata of every
instruction of the compiled step; they change nothing that runs.  One
chokepoint per phase:

* ``FORWARD`` wraps the parameter cast and the loss inside the function
  that ``jax.value_and_grad`` differentiates (``train/loop.py``).  Autodiff
  labels the rest by itself: the backward reads
  ``transpose(jvp(train.forward))`` and a rematerialised op adds
  ``rematted_computation`` to that.
* ``OPTIMIZER`` wraps ``Optimizer.update`` (``optim/optimizers.py``) and the
  loss-scale work of the step: unscale, finite check, skip-or-apply, next
  scale, the cast of the master to the working dtype.
* ``EXCHANGE`` wraps the public exchange methods of ``core/fabric.Fabric``.

``DATA_BATCH`` is a host span (``jax.profiler.TraceAnnotation``) around the
synthesis and placement of a batch (``data/pipeline.py``); it shares the
profiler's clock with the device ops.

``phase_of`` maps an instruction's ``op_name`` to its phase, and
``phases`` gives the phase of each instruction that runs as an op of its
own, from a compiled program's text (``compiled.as_text()``).  The
profiler's trace names a device op by its instruction, so a reducer joins
the two and sums self time by phase.  A fusion's time goes to its root's
phase, so forward work that XLA recomputes inside a fusion rooted in a
backward op counts as backward: time read this way gives recompute a
floor.  ``phase_flops`` counts the work instead: the FLOPs of every matrix
product by its own ``op_name``, inside fusions too.
"""

from __future__ import annotations

import functools
import math
import re

import jax

FORWARD = "train.forward"
OPTIMIZER = "train.optimizer"
EXCHANGE = "train.exchange"
DATA_BATCH = "data.batch"

UNSCOPED = "unscoped"

_TRANSPOSED = "transpose("
_REMATTED = "rematted_computation"


def phase_of(op_name: str) -> str:
    """The phase of one instruction of the step, from its ``op_name``:
    ``forward``, ``backward``, ``recompute``, ``optimizer``, ``exchange``,
    or ``UNSCOPED``."""
    if EXCHANGE in op_name:
        return "exchange"
    if OPTIMIZER in op_name:
        return "optimizer"
    if FORWARD in op_name:
        if _REMATTED in op_name:
            return "recompute"
        if _TRANSPOSED in op_name:
            return "backward"
        return "forward"
    return UNSCOPED


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


_INSTR = re.compile(r"^\s*(ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLEE = re.compile(r"\b(calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([^\s,)}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([^\s,()]+)")
# instructions that never run as a device op of their own
_NO_OP = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}


def _opcode(rhs: str) -> tuple:
    """(opcode, the text from it on) of an instruction, from the text
    after ``=``: its type (a tuple type is parenthesised), then
    ``opcode(operands), attributes``."""
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rhs[i + 1:].lstrip()
    else:
        rest = rhs.partition(" ")[2]
    return rest.partition("(")[0], rest


def _parse(hlo_text: str) -> tuple:
    """``({computation: {instruction: record}}, entry)`` of a module's
    text; the record holds the opcode, the result type, the text from the
    opcode on, the ``op_name``, whether it is the root, its callees as
    ``(kind, computation)`` and its operands."""
    comps, comp, entry = {}, None, None
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{") and " = " not in line:
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            comps[comp] = {}
            if line.startswith("ENTRY"):
                entry = comp
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        opcode, rest = _opcode(m.group(3))
        op = _OP_NAME.search(rest)
        callees = _CALLEE.findall(rest)
        for group in _BRANCHES.findall(rest):
            callees += [("branch", c.strip().lstrip("%"))
                        for c in group.split(",")]
        args = rest.partition("(")[2]
        comps[comp][m.group(2)] = {
            "opcode": opcode, "type": m.group(3)[:len(m.group(3))
                                                 - len(rest)],
            "text": rest, "op": op.group(1) if op else "",
            "root": bool(m.group(1)), "callees": callees,
            "operands": _OPERAND.findall(args.partition("), ")[0])}
    return comps, entry


def _fused_root_op(instrs: dict, root: str) -> str:
    """The ``op_name`` of a fused computation's root, else of the nearest
    instruction the root reads from that has one (a root may be XLA's own
    bitcast, convert or tuple)."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop(0)
        if name in seen or name not in instrs:
            continue
        seen.add(name)
        if instrs[name]["op"]:
            return instrs[name]["op"]
        todo.extend(instrs[name]["operands"])
    return ""


def phases(hlo_text: str) -> dict:
    """``{instruction: phase}`` for each instruction of a compiled module's
    text that runs as an op of its own: in the entry computation or a
    computation it runs for control flow (a loop body, a branch), not
    inside a fusion or a region a reduction applies, and not a parameter,
    constant, tuple or bitcast.

    An op takes the phase of its ``op_name``; a fusion that of its fused
    computation's root, as the fusion's time is the root's work.  An op
    whose ``op_name`` names no phase takes that of the nearest op it feeds
    (XLA's own copies and broadcasts carry no ``op_name``, and autodiff
    makes a backward loop's zero carries outside the scope of the loop
    they feed), else that of the loop or branch that runs it, else it is
    ``UNSCOPED``."""
    comps, _ = _parse(hlo_text)
    sub, caller = set(), {}
    for comp, instrs in comps.items():
        for name, r in instrs.items():
            for kind, callee in r["callees"]:
                if kind in ("calls", "to_apply") and r["opcode"] != "call":
                    sub.add(callee)
                else:
                    caller[callee] = (comp, name)
    users = {c: {} for c in comps}
    for c, instrs in comps.items():
        for name, r in instrs.items():
            for operand in r["operands"]:
                users[c].setdefault(operand, []).append(name)

    def own(comp, name):
        r = comps[comp][name]
        if r["opcode"] == "parameter":  # its op_name is the argument's path
            return UNSCOPED
        fused = [c for kind, c in r["callees"]
                 if kind == "calls" and c in comps]
        if r["opcode"] == "fusion" and fused:
            root = next((n for n, f in comps[fused[0]].items() if f["root"]),
                        None)
            return phase_of(_fused_root_op(comps[fused[0]], root)
                            or r["op"])
        return phase_of(r["op"])

    def phase(comp, name, depth=0):
        seen, todo = set(), [name]
        while todo:
            n = todo.pop(0)
            if n in seen or n not in comps[comp]:
                continue
            seen.add(n)
            ph = own(comp, n)
            if ph != UNSCOPED:
                return ph
            todo.extend(users[comp].get(n, ()))
        if comp in caller and depth < 16:
            return phase(*caller[comp], depth + 1)
        return UNSCOPED

    return {name: phase(comp, name) for comp, instrs in comps.items()
            if comp not in sub for name, r in instrs.items()
            if r["opcode"] not in _NO_OP}


_SHAPE = re.compile(r"\[([0-9,]*)\]")
_CONTRACTING = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_LABELS = re.compile(r"dim_labels=([0-9a-z]+)_([0-9a-z]+)->([0-9a-z]+)")
_WINDOW = re.compile(r"window=\{([^}]*)\}")
_TRIP = re.compile(r'known_trip_count":\{"n":"([0-9]+)"')
_CONSTANT = re.compile(r"^constant\(([0-9]+)\)")


def _dims(type_text: str) -> list:
    m = _SHAPE.search(type_text)
    return [int(d) for d in m.group(1).split(",") if d] if m else []


def _window(text: str, n: int) -> list:
    """Per spatial dimension of a convolution's window: (size, stride,
    padding low, lhs dilation, rhs dilation)."""
    m = _WINDOW.search(text)
    fields = dict(f.split("=", 1) for f in (m.group(1).split() if m else ())
                  if "=" in f)

    def each(key, default):
        v = fields.get(key)
        return [default] * n if v is None else v.split("x")

    pads = [int(p.split("_")[0]) for p in each("pad", "0_0")]
    return list(zip((int(v) for v in each("size", 1)),
                    (int(v) for v in each("stride", 1)), pads,
                    (int(v) for v in each("lhs_dilate", 1)),
                    (int(v) for v in each("rhs_dilate", 1))))


def _taps(size_in, size_out, k, stride, pad, lhs_dil, rhs_dil) -> int:
    """Products one spatial dimension of a convolution computes: the
    (output, kernel) position pairs that land on an input element, not on
    padding or on a hole of the input's dilation.  XLA writes a batched
    matrix product as a convolution whose window slides over the batch
    dimension with such holes, so the window's full size would count it
    many times over."""
    span = (size_in - 1) * lhs_dil
    n = 0
    for j in range(k):
        for o in range(size_out):
            x = o * stride + j * rhs_dil - pad
            n += 0 <= x <= span and x % lhs_dil == 0
    return n


def _flops(instrs: dict, r: dict) -> int:
    """2 × the multiply-adds of a ``dot`` or ``convolution`` (XLA's TPU
    backend writes most matrix products as convolutions), else 0."""
    if r["opcode"] not in ("dot", "convolution") or len(r["operands"]) < 2:
        return 0
    lhs, rhs = (instrs.get(o) for o in r["operands"][:2])
    if lhs is None or rhs is None:
        return 0
    out = _dims(r["type"])
    if r["opcode"] == "dot":
        m = _CONTRACTING.search(r["text"])
        shape = _dims(lhs["type"])
        return 2 * math.prod(out) * (math.prod(
            shape[int(i)] for i in m.group(1).split(",") if i) if m else 1)
    m = _LABELS.search(r["text"])
    if m is None:
        return 0
    in_l, k_l, out_l = m.groups()
    ins, kernel = _dims(lhs["type"]), _dims(rhs["type"])
    spatial = sorted(c for c in out_l if c.isdigit())
    taps = math.prod(
        _taps(ins[in_l.index(d)], out[out_l.index(d)],
              kernel[k_l.index(d)], *w)
        for d, (_, *w) in zip(spatial, _window(r["text"], len(spatial))))
    return 2 * out[out_l.index("b")] * out[out_l.index("f")] \
        * kernel[k_l.index("i")] * taps


def _trip_count(comps: dict, r: dict) -> int:
    """Iterations of a ``while``: XLA's ``known_trip_count``, else the
    constant its condition compares the counter against (``i < n``, as a
    scan's loop reads), else 1."""
    m = _TRIP.search(r["text"])
    if m:
        return int(m.group(1))
    cond = dict(r["callees"]).get("condition")
    instrs = comps.get(cond, {})
    root = next((f for f in instrs.values() if f["root"]), None)
    if root and root["opcode"] == "compare" and "direction=LT" in \
            root["text"]:
        for o in root["operands"]:
            c = _CONSTANT.match(instrs.get(o, {}).get("text", ""))
            if c:
                return int(c.group(1))
    return 1


def phase_flops(hlo_text: str) -> dict:
    """``{phase: FLOPs}`` of one run of a compiled module: every matrix
    product (``dot``, ``convolution``), inside fusions and loop bodies too,
    by the phase of its own ``op_name`` (``phase_of``), times the runs of
    its computation (a loop body runs its trip count).  Recompute counted
    so is the forward work that autodiff's remat repeats, whatever fusion
    XLA put it in."""
    comps, entry = _parse(hlo_text)
    runs = dict.fromkeys(comps, 0)

    def visit(comp, n):
        runs[comp] += n
        for r in comps[comp].values():
            for kind, callee in r["callees"]:
                if callee in comps:
                    visit(callee, n * (_trip_count(comps, r)
                                       if kind == "body" else 1))

    if entry is not None:
        visit(entry, 1)
    out = {}
    for comp, instrs in comps.items():
        for r in instrs.values():
            f = _flops(instrs, r) * runs[comp]
            if f:
                ph = phase_of(r["op"])
                out[ph] = out.get(ph, 0) + f
    return out
