"""Model FLOPs of the traced steps (``flops.train_flops_per_token``: 6·N
plus causal attention, no remat recompute) over the traced window times
the chips times the chip's peak, in %."""


def read(rec, trace):
    if not trace or not rec.get("traced_steps"):
        return None
    work = rec["flops_per_step"] * rec["traced_steps"]
    peak = rec["peaks"]["flops_per_s"] * rec["chips"]
    return 100.0 * work / (trace["window_s"] * peak)
