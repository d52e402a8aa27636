"""Device milliseconds a traced step spends in the step's optimizer phase:
the self time of the ops under the program's ``train.optimizer`` scope
(the update and the loss-scale work), averaged over the chips.  The
phase is the program's (``repro.core.scopes.phases``), joined to the
trace by instruction name (a fusion takes its fused root's); nothing to
read where the trace has no such phase."""


def read(rec, trace):
    t = (trace or {}).get("phase_s", {}).get("optimizer")
    steps = rec.get("traced_steps")
    if not t or not steps:
        return None
    return 1e3 * t / steps
