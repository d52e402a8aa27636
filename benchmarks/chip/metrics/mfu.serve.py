"""Model FLOPs of the prefill and decode calls of the engine steps in the
traced part of the window (real tokens only: ``flops.prefill_flops``,
``flops.decode_flops``), over the host-clock time of those steps times
the chip's peak, in %.  It does not read the offered load."""


def read(rec, trace):
    busy = sum(rec.get("traced_step_s") or [])
    if busy <= 0:
        return None
    peak = rec["peaks"]["flops_per_s"] * rec["chips"]
    return 100.0 * rec["traced_model_flops"] / (busy * peak)
