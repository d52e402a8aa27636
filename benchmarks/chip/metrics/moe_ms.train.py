"""Device milliseconds a traced step spends under the program's
``moe.experts`` scope (``models/layers.moe``: the router, the dispatch,
the grouped matmuls and the combine), forward, backward and recompute
together, averaged over the chips.  The scope is joined to the trace by
instruction name (a fusion takes its fused root's); nothing to read where
the trace has no such scope."""

SCOPE = "moe.experts"


def read(rec, trace):
    t = (trace or {}).get("scope_s", {}).get(SCOPE)
    steps = rec.get("traced_steps")
    if not t or not steps:
        return None
    return 1e3 * t / steps
