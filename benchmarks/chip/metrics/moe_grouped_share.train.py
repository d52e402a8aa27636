"""Share of the expert layers traced into the step that took the
dropless grouped path, in %: grouped / (grouped + capacity) of the
program's ``/repro/moe_path/`` counters (``models/layers.moe`` records
its path for each traced layer), taken over the build of the step.
Nothing to read where no expert layer was traced."""

PATH = "/repro/moe_path/"


def read(rec, trace):
    counters = rec.get("counters") or {}
    grouped = counters.get(PATH + "grouped", 0)
    capacity = counters.get(PATH + "capacity", 0)
    if grouped + capacity <= 0:
        return None
    return 100.0 * grouped / (grouped + capacity)
