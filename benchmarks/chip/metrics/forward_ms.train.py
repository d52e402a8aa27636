"""Device milliseconds a traced step spends in the step's forward phase:
the self time of the ops under the program's ``train.forward`` scope,
outside autodiff's transpose and remat, averaged over the chips.  The
phase is the program's (``repro.core.scopes.phases``), joined to the
trace by instruction name (a fusion takes its fused root's); nothing to
read where the trace has no such phase."""


def read(rec, trace):
    t = (trace or {}).get("phase_s", {}).get("forward")
    steps = rec.get("traced_steps")
    if not t or not steps:
        return None
    return 1e3 * t / steps
