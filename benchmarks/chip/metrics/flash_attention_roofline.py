"""Share of the chip's matrix peak that the flash-attention kernels
(``kernels/flash_attention.py``) reach in the traced steps, in %: the
attention work of their calls over the summed device time of all three
kernels times the chip's peak.

The work reads the same whatever implements it.  Each call of
``flash_attention_fwd`` counts the causal forward of one layer,
F = 4·H·Dh·B·L(L+1)/2 (``flops.causal_attn_flops``, B the rows of one
chip); each call of ``flash_attention_dkv`` counts 2F, the four causal
products of one backward; the dQ kernel's calls count in the time only.
The calls are counted from the trace (``op_n``), the time is their self
time (``op_s``), both averaged over the chips.  At qwen2-1.5b's widths and
4 × 2048 tokens F ≈ 51.6 GFLOP, and a step of 4 layers makes 8 forward
calls (4 forward, 4 recompute) and 4 backward: 16F ≈ 825 GFLOP.

The kernels are found by their instruction names, which the program's
``pallas_call(name=...)`` gives; a kernel renamed in the program reads
nothing here until a benchmark change names it.  Nothing to read where no
kernel ran."""

import re

from chipbench.flops import causal_attn_flops
from chipbench.trace_reduce import instruction

KERNEL = re.compile(r"flash_attention_(fwd|dq|dkv)(\.\d+)?")
WORK = {"fwd": 1, "dq": 0, "dkv": 2}  # in units of F


def read(rec, trace):
    if not trace or "seq_len" not in rec:
        return None
    t = units = 0.0
    for name, seconds in trace["op_s"].items():
        m = KERNEL.fullmatch(instruction(name))
        if m:
            t += seconds
            units += WORK[m.group(1)] * trace["op_n"].get(name, 0)
    if t <= 0 or units <= 0:
        return None
    work = units * causal_attn_flops(rec["dims"], rec["batch_per_chip"],
                                     rec["seq_len"])
    return 100.0 * work / (t * rec["peaks"]["flops_per_s"])
