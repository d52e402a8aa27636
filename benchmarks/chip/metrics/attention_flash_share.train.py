"""Share of the attention layers traced into the step that took the
Pallas flash kernel, in %: flash / (flash + dense) of the program's
``/repro/attention_path/`` counters (``models/layers.attention`` records
its path for each traced layer), taken over the build of the step.
Nothing to read where no attention layer was traced."""

PATH = "/repro/attention_path/"


def read(rec, trace):
    counters = rec.get("counters") or {}
    flash = counters.get(PATH + "flash", 0)
    dense = counters.get(PATH + "dense", 0)
    if flash + dense <= 0:
        return None
    return 100.0 * flash / (flash + dense)
