"""Device milliseconds a traced step spends in the step's backward phase:
the self time of the ops of autodiff's transpose of ``train.forward``,
outside remat, averaged over the chips.  The phase is the program's
(``repro.core.scopes.phases``), joined to the trace by instruction name
(a fusion takes its fused root's); nothing to read where the trace has
no such phase."""


def read(rec, trace):
    t = (trace or {}).get("phase_s", {}).get("backward")
    steps = rec.get("traced_steps")
    if not t or not steps:
        return None
    return 1e3 * t / steps
