"""Median, over the requests due in the window, of the time from when a
request was due to the start of the engine step in which it left the
engine's queue, in ms (host clock, read between steps)."""

import numpy as np


def read(rec, trace):
    waits = rec.get("admit_wait_s") or []
    return float(np.median(waits)) * 1e3 if waits else None
