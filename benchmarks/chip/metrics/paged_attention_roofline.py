"""Share of its roofline that the Pallas paged-attention kernel reaches in
the traced part of the window, in %: the least time the chip could take
for the decode calls' attention, max(bytes / peak bytes/s, FLOPs / peak
FLOP/s) with ``flops.paged_attention_cost`` per layer, over the kernel's
summed device time (ops whose instruction is named ``paged_attention``,
not the ops that read its output).  The bytes bound it: one query token
reads a whole context."""

import re

from chipbench.trace_reduce import instruction

KERNEL = re.compile(r"paged_attention(\.\d+)?")


def read(rec, trace):
    calls = rec.get("traced_paged_attention") or []
    if not trace or not calls:
        return None
    t = sum(v for k, v in trace["op_s"].items()
            if KERNEL.fullmatch(instruction(k)))
    if t <= 0:
        return None
    layers = rec["dims"]["layers"]
    fl = layers * sum(f for f, _ in calls)
    by = layers * sum(b for _, b in calls)
    p = rec["peaks"]
    bound = max(by / p["hbm_bytes_per_s"], fl / p["flops_per_s"])
    return 100.0 * bound / t
