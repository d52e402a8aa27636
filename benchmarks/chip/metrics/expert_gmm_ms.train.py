"""Device milliseconds a traced step spends in the grouped-matmul kernels
of the expert layer (``kernels/grouped_matmul.py``: ``expert_gmm`` for
the forward products and their input gradients, ``expert_tgmm`` for the
weight gradients), found by instruction name, self time averaged over
the chips.

A time and not a share of the peak: the kernels' work follows the rows
that the router sends to the held experts, which the trace does not show
and which training moves (a count at the expected rows read 1964 % of
the peak on a v5e, in a run whose router had turned away from the held
experts).  Nothing to read where no such kernel ran."""

import re

from chipbench.trace_reduce import instruction

KERNEL = re.compile(r"expert_t?gmm(\.\d+)?")


def read(rec, trace):
    steps = rec.get("traced_steps")
    if not trace or not steps:
        return None
    t = sum(s for name, s in trace["op_s"].items()
            if KERNEL.fullmatch(instruction(name)))
    if t <= 0:
        return None
    return 1e3 * t / steps
