"""Share of the expert layer's row work, in %, that ran on the compact
rows: device time under the program's ``moe.compact`` scope over that
under ``moe.compact`` and ``moe.spill`` together (``models/layers
._held_rows``: the row buffers hold the first sorted assignments where
the held ones fit, else every assignment), forward, backward and
recompute together.  Scopes are joined to the trace by instruction name
(an op inside either branch of the choice takes the branch's scope).
Nothing to read where the trace has neither scope: a layer that holds
all its experts, or a program without the choice."""

COMPACT, SPILL = "moe.compact", "moe.spill"


def read(rec, trace):
    scopes = (trace or {}).get("scope_s", {})
    compact, spill = scopes.get(COMPACT, 0.0), scopes.get(SPILL, 0.0)
    if compact + spill <= 0:
        return None
    return 100.0 * compact / (compact + spill)
