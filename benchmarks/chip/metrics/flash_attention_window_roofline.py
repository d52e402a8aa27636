"""Share of the chip's matrix peak that the sliding-window flash-attention
kernels (``kernels/flash_attention.py`` under a window) reach in the
traced steps, in %: the windowed attention work of their calls over the
summed device time of all three kernels times the chip's peak.

Read as ``flash_attention_roofline`` reads the plain causal kernels: each
call of ``flash_attention_window_fwd`` counts the windowed forward of one
layer, F = 4·H·Dh·B·Σ_i min(i + 1, window) (the family's
``window_attn_flops``, B the rows of one chip); each call of
``flash_attention_window_dkv`` counts 2F; the dQ kernel's calls count in
the time only.  Calls from the trace (``op_n``), time as self time
(``op_s``), both averaged over the chips.  Nothing to read where no such
kernel ran, or where the cell's family counts no windowed attention."""

import re

from chipbench import spec
from chipbench.trace_reduce import instruction

KERNEL = re.compile(r"flash_attention_window_(fwd|dq|dkv)(\.\d+)?")
WORK = {"fwd": 1, "dq": 0, "dkv": 2}  # in units of F


def read(rec, trace):
    dims = rec.get("dims") or {}
    if not trace or "seq_len" not in rec or "family" not in dims:
        return None
    fam = spec.family({"family": dims["family"]})
    if not hasattr(fam, "window_attn_flops"):
        return None
    t = units = 0.0
    for name, seconds in trace["op_s"].items():
        m = KERNEL.fullmatch(instruction(name))
        if m:
            t += seconds
            units += WORK[m.group(1)] * trace["op_n"].get(name, 0)
    if t <= 0 or units <= 0:
        return None
    work = units * fam.window_attn_flops(dims, rec["batch_per_chip"],
                                         rec["seq_len"])
    return 100.0 * work / (t * rec["peaks"]["flops_per_s"])
