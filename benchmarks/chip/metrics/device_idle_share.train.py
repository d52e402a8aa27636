"""1 − busy / window of the traced steps, in %: busy is the union of the
intervals in which an op ran on a chip, averaged over the chips."""


def read(rec, trace):
    if not trace or trace.get("idle_share") is None:
        return None
    return 100.0 * trace["idle_share"]
