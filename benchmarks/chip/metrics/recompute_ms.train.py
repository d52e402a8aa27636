"""Device milliseconds a traced step spends in the step's recompute phase:
the self time of the ops that remat recomputes inside the backward of
``train.forward`` (a floor: forward work that XLA fuses into an op
rooted in the backward counts as backward), averaged over the chips.
The phase is the program's (``repro.core.scopes.phases``), joined to the
trace by instruction name (a fusion takes its fused root's); nothing to
read where the trace has no such phase."""


def read(rec, trace):
    t = (trace or {}).get("phase_s", {}).get("recompute")
    steps = rec.get("traced_steps")
    if not t or not steps:
        return None
    return 1e3 * t / steps
