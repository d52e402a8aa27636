"""Time in which an all-reduce, reduce-scatter, all-gather, all-to-all or
collective-permute ran (or was in flight) on a chip and no other op ran
there, over the traced window, in %, averaged over the chips.  Nothing
to read where no collective ran."""


def read(rec, trace):
    if not trace or trace["collective_s"] <= 0:
        return None
    return 100.0 * trace["exposed_collective_s"] / trace["window_s"]
